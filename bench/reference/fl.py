"""Plain reference of GPFL's federated rounds (paper Algorithm 1, Eq. 1-8).

The MLP's initial weights follow the algorithm's own rule from the seed:
``key(seed)`` split into (carry, w⁰) and, for GPFL, (carry, init phase);
each weight matrix is normal / sqrt(fan_in), each bias zero, one key per
leaf in sorted name order.  Then:

* init phase (GPFL): every client trains once from w⁰; the seed
  direction is the mean of their momenta and each client's seed GP is
  its momentum projected on it (Eq. 3);
* each round: split the carry key; select K clients (round 0: top-K
  seed GP; later: GPCB value, Eq. 6-7, with never-selected clients on a
  plateau ordered by the round's tie-break draw; ``random``: the round's
  draw of K of N); train each for ``local_iters`` heavy-ball steps on
  batches drawn with ``randint`` from its key (Eq. 1-2, weight decay
  added to the gradient); FedAvg; direction ← γ·direction + (w − w')/η;
  evaluate on the held-out set; score the cohort's momenta against the
  previous direction (Eq. 3); update the bandit (Eq. 5 softmax, Eq. 8
  re-calibration).

Given the cohorts a run chose (``follow``), the reference scores and
ranks every client as above, but trains the cohort the run trained, so
that it stays on the run's trajectory for the whole run: the run's
choice of each cohort is then judged against the reference's scores of
that round, and everything the cohort produces against the reference's.

Arrays are float32 at ``highest`` precision; ``dtype=bfloat16`` gives the
control.  The bandit runs in float64 numpy.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

#: the init phase keys its clients in chunks of this many: each chunk's
#: keys split from ``fold_in(init_key, first client of the chunk)``
INIT_CHUNK = 25


def mlp_init(key, dims, dtype):
    keys = jax.random.split(key, 2 * (len(dims) - 1))
    params = {}
    for i in range(len(dims) - 1):
        w = jax.random.normal(keys[2 * i + 1], (dims[i], dims[i + 1]),
                              jnp.float32) / math.sqrt(dims[i])
        params[f"fc{i}"] = {"b": jnp.zeros((dims[i + 1],), dtype),
                            "w": w.astype(dtype)}
    return params


def mlp_logits(params, x):
    n = len(params)
    h = x.reshape(x.shape[0], -1)
    for i in range(n):
        h = h @ params[f"fc{i}"]["w"] + params[f"fc{i}"]["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def xent(params, x, y):
    logits = mlp_logits(params, x).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def _client(params0, x, y, size, key, *, iters, batch, lr, wd, mom,
            keep, frozen):
    """One client's local training → (w_i, d_i); ``keep`` < ``batch``
    plants the fault of a batch whose other rows are left out, ``frozen``
    that of steps that return their state unchanged."""
    def one(carry, k):
        if frozen:
            return carry, None
        p, m = carry
        idx = jax.random.randint(k, (batch,), 0,
                                 jnp.maximum(size, 1))[:keep]
        g = jax.grad(xent)(p, x[idx], y[idx])
        m = jax.tree.map(lambda mi, gi, pi: mom * mi + (gi + wd * pi),
                         m, g, p)
        p = jax.tree.map(lambda pi, mi: pi - lr * mi, p, m)
        return (p, m), None

    m0 = jax.tree.map(jnp.zeros_like, params0)
    (p, m), _ = jax.lax.scan(one, (params0, m0),
                             jax.random.split(key, iters))
    return p, m


def _flat(tree):
    return jnp.concatenate([a.reshape(a.shape[0], -1).astype(jnp.float32)
                            for a in jax.tree.leaves(tree)], axis=1)


def reference_rounds(config: dict, selector: str, seed: int, x_tab, y_tab,
                     eval_x, eval_y, rounds: int, dtype=jnp.float32,
                     precision: str = "highest", fault=None,
                     follow=None) -> dict:
    """Follow the first ``rounds`` rounds → per round the cohort the
    reference picks (``ids``), its selection scores (GPFL), the eval loss
    and accuracy; and each client's latest GP score after the last round
    (``latest``, GPFL).

    ``follow`` (rounds × K client ids): train these cohorts instead of
    the reference's picks.  ``dtype``/``precision`` below
    float32/``highest`` give the control; ``fault`` plants one:
    ``"unchanged"`` (local steps leave the state as it was),
    ``"half_cohort"`` (FedAvg over the first K − ⌊K/2⌋ of the round's K
    client updates, the rest left out), ``"half_local_batch"`` (half of
    each client's local batch left out, the mean over the rest),
    ``"answer"`` (one selected client replaced by one that was not
    selected, every round), ``"gp_half"`` (GP scores projected over the
    first half of the flattened parameters only)."""
    m, fl = config["model"], config["fl"]
    dims = ([int(np.prod(m["input_shape"]))] + list(m["hidden"])
            + [m["num_classes"]])
    N, K = fl["n_clients"], fl["clients_per_round"]
    lr, mom = fl["lr"], fl["momentum"]
    batch = fl["local_batch_size"]
    train = jax.jit(jax.vmap(functools.partial(
        _client, iters=fl["local_iters"], batch=batch,
        lr=lr, wd=fl["weight_decay"], mom=mom,
        keep=batch // 2 if fault == "half_local_batch" else batch,
        frozen=fault == "unchanged"),
        in_axes=(None, 0, 0, 0, 0)))

    @jax.jit
    def evaluate(p, x, y):
        logits = mlp_logits(p, x).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32)), \
            loss

    def project(d, dvec):
        """Eq. 3 of the rows of ``d`` on ``dvec``."""
        if fault == "gp_half":
            d, dvec = d[:, :d.shape[1] // 2], dvec[:dvec.shape[0] // 2]
        return d @ dvec / jnp.maximum(jnp.linalg.norm(dvec), 1e-12)

    x_tab = jnp.asarray(x_tab, dtype)
    y_tab = jnp.asarray(y_tab)
    sizes = jnp.full((N,), x_tab.shape[1], jnp.int32)
    ex, ey = jnp.asarray(eval_x, dtype), jnp.asarray(eval_y)
    rng = np.random.default_rng(seed)
    out = {"ids": [], "scores": [], "loss": [], "acc": []}
    with jax.default_matmul_precision(precision):
        key = jax.random.key(seed)
        key, k0 = jax.random.split(key)
        w = mlp_init(k0, dims, dtype)
        direction = jax.tree.map(jnp.zeros_like, w)
        latest = np.zeros(N)
        if selector == "gpfl":
            key, kinit = jax.random.split(key)
            moms = []
            for ofs in range(0, N, INIT_CHUNK):
                n = min(INIT_CHUNK, N - ofs)
                ks = jax.random.split(jax.random.fold_in(kinit, ofs), n)
                _, d = train(w, x_tab[ofs:ofs + n], y_tab[ofs:ofs + n],
                             sizes[ofs:ofs + n], ks)
                moms.append(_flat(d))
            moms = jnp.concatenate(moms)
            dvec = jnp.mean(moms, axis=0)
            latest = np.asarray(project(moms, dvec), np.float64)
            direction = _unflat(dvec, w)
        reward_sum, count = np.zeros(N), np.zeros(N)
        bround, prev_acc, prev_loss = 0.0, 0.0, 0.0
        for t in range(rounds):
            key, kt = jax.random.split(key)
            scores = None
            if selector == "gpfl":
                jitter = (rng.random(N) if t > 0 else np.zeros(N)
                          ).astype(np.float32)
                if t == 0:
                    scores = latest.copy()
                else:
                    n_ = max(bround, 1.0)
                    alpha = (fl["rho"] * bround
                             / max(1.0, float(fl["rounds"])))
                    with np.errstate(divide="ignore"):
                        u = (reward_sum / np.maximum(count, 1.0) + alpha
                             * np.sqrt(2.0 * math.log(n_)
                                       / np.maximum(count, 1e-9)))
                    u = np.where(count > 0, u,
                                 1e9 + jitter.astype(np.float64) * 1e12)
                    scores = u + jitter * 1e-9
                ids = np.argsort(-scores, kind="stable")[:K]
            elif selector == "random":
                ids = rng.choice(N, size=K, replace=False)
            else:
                raise ValueError(f"no reference for selector {selector!r}")
            if fault == "answer":
                ids = ids.copy()
                ids[-1] = next(c for c in range(N) if c not in set(ids))
            out["ids"].append(np.asarray(ids))
            if follow is not None:
                ids = np.asarray(follow[t])
            ids_j = jnp.asarray(ids, jnp.int32)
            w_i, d_i = train(w, x_tab[ids_j], y_tab[ids_j], sizes[ids_j],
                             jax.random.split(kt, K))
            keep = K - K // 2 if fault == "half_cohort" else K
            w_new = jax.tree.map(lambda a: jnp.mean(a[:keep], axis=0), w_i)
            dir_prev = direction
            direction = jax.tree.map(
                lambda d, a, b: mom * d + (a - b) * (1.0 / lr),
                direction, w, w_new)
            w = w_new
            acc, loss = (float(v) for v in evaluate(w, ex, ey))
            if selector == "gpfl":
                dvec = _flat(jax.tree.map(lambda a: a[None], dir_prev))[0]
                latest[ids] = np.asarray(project(_flat(d_i), dvec),
                                         np.float64)
                mask = np.zeros(N)
                mask[ids] = 1.0
                e = np.exp(latest - latest.max())
                mu = e / e.sum() * mask
                if abs(acc - prev_acc) > 1e-9:
                    factor = 2.0 * math.exp(np.clip(acc - prev_acc, -10, 10))
                else:
                    factor = math.exp(np.clip(loss - prev_loss, -10, 10))
                mu = np.clip(mu * factor, 0.0, 1.0)
                reward_sum += mask * mu
                count += mask
                bround += 1.0
                prev_acc, prev_loss = acc, loss
            out["scores"].append(scores)
            out["loss"].append(loss)
            out["acc"].append(acc)
        if selector == "gpfl":
            out["latest"] = latest
    return out


def _unflat(vec, like):
    """Split a flat vector back into ``like``'s tree (leaf order)."""
    leaves, treedef = jax.tree.flatten(like)
    parts, ofs = [], 0
    for a in leaves:
        parts.append(vec[ofs:ofs + a.size].reshape(a.shape).astype(a.dtype))
        ofs += a.size
    return jax.tree.unflatten(treedef, parts)


def compare(prog: dict, ref: dict, k: int, selection_rounds=None) -> dict:
    """The compared numbers of one run; ``ref`` followed ``prog``'s cohorts.

    * ``selection``: per round, the widest gap by which a client the
      program selected lies below the reference's K-th best score, over
      the range of the reference's scores (GPFL); for ``random`` 1 where
      the cohorts differ.  Read over the first ``selection_rounds``
      rounds (all when ``None``).
    * ``loss``: the widest relative gap of the eval loss over the rounds.
    * ``gp`` (GPFL): the relative error of the clients' latest GP scores
      after the last round, ‖prog − ref‖ / ‖ref‖ over the N clients.
    """
    n_sel = len(ref["ids"]) if selection_rounds is None else selection_rounds
    sel_gap = 0.0
    for t in range(min(n_sel, len(ref["ids"]))):
        ids_p = np.asarray(prog["ids"][t])
        s = ref["scores"][t]
        if s is not None:
            s = np.asarray(s, np.float64)
            kth = np.sort(s)[-k]
            scale = max(float(s.max() - s.min()), 1e-30)
            sel_gap = max(sel_gap, max(0.0, float(np.max(
                kth - s[ids_p]))) / scale)
        elif set(ids_p.tolist()) != set(np.asarray(ref["ids"][t]).tolist()):
            sel_gap = 1.0
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    by_round = np.abs(lp - lr) / np.abs(lr)
    out = {"selection": sel_gap, "loss": float(np.max(by_round)),
           "loss_by_round": by_round.tolist()}
    if "latest" in ref and "latest" in prog:
        gp_p = np.asarray(prog["latest"], np.float64)
        gp_r = np.asarray(ref["latest"], np.float64)
        out["gp"] = float(np.linalg.norm(gp_p - gp_r)
                          / max(np.linalg.norm(gp_r), 1e-30))
    return out
