"""Plain reference of Mamba-2 (arXiv:2405.21060) and of one GPFL-gated or
plain momentum step on it.

The SSD mixer is written in its quadratic ("attention") form over the
whole sequence, not in chunks:

    y_t = Σ_{s≤t} (C_t·B_s) · exp(Σ_{r=s+1..t} dt_r·A) · dt_s · x_s + D·x_t

with A = −exp(A_log), dt = softplus(dt_raw + dt_bias), after a width-4
depthwise causal conv with SiLU over (x, B, C); then the SiLU(z)-gated
RMSNorm and the out projection, residual around an RMSNorm'd input.  The
tied embedding is the output head; the loss is the mean next-token
cross-entropy.  Layers run in a scan, each rematerialised in the backward
pass, so that the 48 layers fit one chip.

The step follows the paper's datacenter form: the batch is ``n_groups``
equal row slices ("virtual clients"); each slice's gradient is taken
separately; its GP score is its gradient projected on the momentum
(Eq. 3); GPCB (Eq. 6-7, never-selected slices first, ordered by score)
picks ``k_select`` of them; the step descends on the mean of their
gradients with heavy-ball momentum (Eq. 1-2); the bandit takes the
softmax of the scores as rewards, re-calibrated by the loss change
(Eq. 5, 8).  The plain step descends on the mean of all slices.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

CONV_WIDTH = 4
EPS = 1e-6


def _rmsnorm(x, scale):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + EPS) * scale


def _layer(x, p, cfg):
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    st = cfg["state_size"]
    hd = cfg["head_dim"]
    H = d_in // hd
    B, S, _ = x.shape
    q = p["ssd"]
    h = _rmsnorm(x, p["norm1"]["scale"])
    proj = h @ q["w_in"]
    z = proj[..., :d_in]
    xbc = proj[..., d_in:2 * d_in + 2 * st]
    dt = proj[..., 2 * d_in + 2 * st:]
    pad = jnp.pad(xbc, ((0, 0), (CONV_WIDTH - 1, 0), (0, 0)))
    conv = q["conv_b"] + sum(pad[:, i:i + S] * q["conv_w"][i]
                             for i in range(CONV_WIDTH))
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_in].reshape(B, S, H, hd)
    bm = xbc[..., d_in:d_in + st]
    cm = xbc[..., d_in + st:]
    dt = jax.nn.softplus(dt + q["dt_bias"])                    # (B,S,H)
    a = -jnp.exp(q["a_log"])                                   # (H,)
    cum = jnp.cumsum(dt * a, axis=1)                           # (B,S,H)
    causal = jnp.tril(jnp.ones((S, S), bool))
    seg = cum[:, :, None, :] - cum[:, None, :, :]              # (B,t,s,H)
    decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", cm, bm)
    w = cb[..., None] * decay * dt[:, None, :, :]              # (B,t,s,H)
    y = jnp.einsum("btsh,bshp->bthp", w, xs) + q["d_skip"][:, None] * xs
    y = y.reshape(B, S, d_in) * jax.nn.silu(z)
    y = _rmsnorm(y, q["norm"])
    return x + y @ q["w_out"]


def loss(params, tokens, labels, cfg):
    """Mean next-token cross-entropy of the whole model."""
    emb = params["embed"]["embedding"]
    x = emb[tokens]
    layer = jax.checkpoint(lambda x, p: (_layer(x, p, cfg), None))
    x, _ = jax.lax.scan(layer, x, params["stack"]["pos0"])
    x = _rmsnorm(x, params["final_norm"]["scale"])
    logits = (x @ emb.T).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def gpcb_order(reward_sum, count, rnd, total_rounds, rho, scores, k):
    """GPCB pick of ``k`` arms: never-selected arms first (by score), then
    the others by GPCB value; ties to the lower index."""
    n = max(rnd, 1.0)
    alpha = rho * rnd / max(1.0, float(total_rounds))
    with np.errstate(divide="ignore"):
        u = (reward_sum / np.maximum(count, 1.0)
             + alpha * np.sqrt(2.0 * math.log(n) / np.maximum(count, 1e-9)))
    unseen = count <= 0
    key = [(0 if unseen[i] else 1, -(scores[i] if unseen[i] else u[i]), i)
           for i in range(len(count))]
    return [i for *_, i in sorted(key)[:k]]


class Reference:
    """The reference's state and its steps, on the device."""

    def __init__(self, cfg: dict, train: dict, params, *,
                 dtype=jnp.float32, precision: str = "highest",
                 fault=None):
        """``dtype``/``precision`` below float32/``highest`` give the
        control; ``fault`` plants a fault (``"half_batch"``: every other
        row of each group left out; ``"answer"``: the gated selection
        replaced by arms it did not pick)."""
        self.cfg, self.train, self.dtype = cfg, train, dtype
        self.fault = fault
        self.precision = precision
        self.params = jax.tree.map(lambda a: a.astype(dtype), params)
        self.momentum = jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params)
        G = train["n_groups"]
        self.reward_sum, self.count = np.zeros(G), np.zeros(G)
        self.round, self.prev_loss = 0.0, 0.0
        with jax.default_matmul_precision(precision):
            self._vg = jax.jit(jax.value_and_grad(
                lambda p, t, lab: loss(p, t, lab, cfg)))
        self._dot = jax.jit(lambda a, b: sum(
            jnp.sum(x.astype(jnp.float32) * y) for x, y in
            zip(jax.tree.leaves(a), jax.tree.leaves(b))))
        self._upd = jax.jit(self._update, donate_argnums=(0, 1))

    def _update(self, params, momentum, grads):
        lr, gamma = self.train["lr"], self.train["gamma"]
        m = jax.tree.map(lambda mi, g: gamma * mi + g.astype(jnp.float32),
                         momentum, grads)
        p = jax.tree.map(lambda pi, mi: (pi.astype(jnp.float32) - lr * mi)
                         .astype(pi.dtype), params, m)
        return p, m

    def step(self, tokens, labels, gated: bool) -> dict:
        """One step on a host batch → loss, scores and selection."""
        t = self.train
        G, k = t["n_groups"], t["k_select"]
        per = tokens.shape[0] // G
        losses, grads = [], []
        for g in range(G):
            sl = slice(g * per, (g + 1) * per,
                       2 if self.fault == "half_batch" else 1)
            with jax.default_matmul_precision(self.precision):
                lv, gr = self._vg(self.params, jnp.asarray(tokens[sl]),
                                  jnp.asarray(labels[sl]))
            losses.append(float(lv))
            grads.append(gr)
        mn = math.sqrt(float(self._dot(self.momentum, self.momentum)))
        scores = np.array([float(self._dot(gr, self.momentum)) / max(mn, 1e-12)
                           for gr in grads])
        loss_mean = float(np.mean(losses))
        if gated:
            sel = gpcb_order(self.reward_sum, self.count, self.round,
                             t["total_rounds"], t["rho"], scores, k)
            if self.fault == "answer":
                sel = [i for i in range(G) if i not in sel][:k]
        else:
            sel = list(range(G))
        mean_grad = jax.tree.map(lambda *gs: sum(gs) / len(gs),
                                 *[grads[i] for i in sel])
        del grads
        self.params, self.momentum = self._upd(self.params, self.momentum,
                                               mean_grad)
        if gated:
            mask = np.zeros(G)
            mask[sel] = 1.0
            e = np.exp(scores - scores.max())
            mu = e / e.sum() * mask
            mu = np.clip(mu * math.exp(np.clip(loss_mean - self.prev_loss,
                                               -10, 10)), 0.0, 1.0)
            self.reward_sum += mask * mu
            self.count += mask
            self.round += 1.0
            self.prev_loss = loss_mean
        return {"loss": loss_mean, "scores": scores, "selected": sel}
