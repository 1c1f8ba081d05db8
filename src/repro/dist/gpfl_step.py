"""The GPFL-gated datacenter train step — Eq. 1-3 + GPCB, all inside one jit.

Virtual clients = gradient groups
---------------------------------
The global batch is split into ``n_groups`` equal row-slices; each slice is a
heterogeneous "virtual client" (the launch scripts feed each group from a
distinct synthetic domain).  One jitted step then performs the whole GPFL
round that the FL simulation does host-side in ``core/selector.py``:

1. **GP scores** (Eq. 3): every group's gradient is projected onto the
   momentum buffer ``d`` — the global descent direction of Eq. 1.
2. **GPCB gating** (Eq. 6-8): the bandit carried in ``TrainState.bandit``
   turns scores into rewards and picks the top-``k_select`` groups.
3. **Gated MGD update** (Eq. 1-2): only the selected groups' gradients enter
   the momentum update.

jvp-vs-grads equivalence
------------------------
Two implementations of step 1 are provided and agree numerically:

* ``impl="grads"`` materialises every group's gradient pytree (K backward
  passes), stacks them leafwise, and computes ``<g_i, d>/|d|`` directly —
  optionally through the Pallas ``gp_projection`` kernel
  (``score_kernel=True``).
* ``impl="jvp"`` never materialises per-group gradients: ``<∇L_i, d>`` is
  the directional derivative of the per-group loss vector along ``d``, so ONE
  forward-mode pass yields every score at once (a K× gradient-memory saving —
  the selected groups' combined gradient then costs a single backward pass of
  the mask-weighted loss).  Formally, with ``L(p) = (L_1(p), …, L_K(p))``::

      jvp(L, p, d)[1] == (<∇L_1, d>, …, <∇L_K, d>)     (exactly Eq. 3·|d|)

  and ``∇(Σ_i m_i L_i / Σm) == Σ_i m_i ∇L_i / Σm`` ties the jvp-side update
  to the grads-side masked average.

In-jit GPCB gating contract
---------------------------
This mirrors the host-side selector contract documented in
``core/selector.py``, with every rule expressed as a jit-safe array op:

* never-selected groups carry ``+inf`` GPCB value (must-explore); inside jit
  selection uses a two-level rank order — every never-selected arm outranks
  every seen arm, never-selected arms are ordered by their *current* GP
  score, seen arms by GPCB value — so forced exploration is ordered by data
  quality.  At step 0 (zero momentum ⇒ all scores exactly 0) this degrades
  to deterministic index order, keeping both impls bit-identical in their
  selection.
* rewards are the Eq. 5 softmax of the latest GP scores over ALL groups,
  masked to the selected ones, then re-calibrated by loss progress (Eq. 8 —
  the datacenter has no eval accuracy, so the loss branch is always taken).
* the bandit observes (mask, calibrated rewards, loss) every step via
  ``gpcb.update_state`` — also when ``gate=False``, so an ungated run still
  logs what GPFL *would* have selected.

``gate=False`` short-circuits the update path to the exact
``make_plain_train_step`` computation (same closure, same
``value_and_grad``, same MGD arithmetic), so the two are bit-identical —
scores and bandit bookkeeping ride along as pure observers.

Every step names its stages with ``jax.named_scope``: ``gpfl_score`` (the
scoring pass), ``gpfl_gate`` (GPCB selection and the bandit's update),
``step_grad`` (the gradient the update applies) and ``step_update`` (MGD),
so a profiler trace puts each device op under one of them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import flat as flat_mod
from repro.core import gp, gpcb
from repro.dist.state import TrainState
from repro.optim.sgd import MGDState, mgd_update
from repro.utils.pytree import tree_global_norm

def _loss_kwargs(rules, remat, unroll, ce_chunks):
    kw = dict(rules=rules, remat=remat)
    if unroll:
        kw["unroll"] = True
    if ce_chunks:
        kw["ce_chunks"] = ce_chunks
    return kw


def _constrain(tree, specs):
    """with_sharding_constraint by a PartitionSpec tree (no-op without specs)."""
    if specs is None:
        return tree
    flat, treedef = jax.tree.flatten(tree)
    sflat, _ = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat) == len(sflat), "grad_specs does not match the grad tree"
    return jax.tree.unflatten(treedef, [
        jax.lax.with_sharding_constraint(x, s) for x, s in zip(flat, sflat)])


def _group_batches(batch, n_groups: int):
    """Split every batch leaf into ``n_groups`` equal leading-dim slices."""
    B = jax.tree.leaves(batch)[0].shape[0]
    if B % n_groups:
        raise ValueError(
            f"batch size {B} is not divisible by n_groups={n_groups}; "
            f"virtual clients must receive equal shares")
    per = B // n_groups
    return [jax.tree.map(lambda a: a[g * per:(g + 1) * per], batch)
            for g in range(n_groups)]


def _select(bandit: gpcb.BanditState, scores, k_select: int,
            total_rounds: int, rho: float, explore_unseen: bool = True):
    """GPCB top-k inside jit → (mask, gpcb values).  See the module doc for
    the never-selected / step-0 tie-breaking contract.

    The two-level order (never-selected arms first when ``explore_unseen``,
    last otherwise; within each level by current GP score resp. GPCB value)
    is built from integer RANKS rather than by adding scores to a large
    constant — f32 has a ~64 ulp at 1e9, so ``1e9 + score`` would absorb any
    |score| < 32 and the score ordering would silently degrade to index
    order.  ``explore_unseen=False`` is the apply-step (pure-exploitation)
    mode: a step that gathers no evidence must not burn the must-explore
    rule on arms it cannot observe."""
    with jax.named_scope("gpfl_gate"):
        u = gpcb.gpcb_values(bandit, total_rounds, rho)
        unseen = jnp.isinf(u)
        secondary = jnp.where(unseen, scores, u)
        n = secondary.shape[0]
        pos = jnp.argsort(-secondary)    # best first; stable ⇒ ties → lower index
        rank = jnp.argsort(pos)          # 0 = best
        unseen_level = 2.0 * n if explore_unseen else 0.0
        vals = jnp.where(unseen, unseen_level, float(n)) - rank  # small exact ints
        _, idx = jax.lax.top_k(vals, k_select)
        mask = jnp.zeros(vals.shape, jnp.float32).at[idx].set(1.0)
    return jax.lax.stop_gradient(mask), u


def _observe(bandit: gpcb.BanditState, mask, scores, loss_scalar,
             rewards=None):
    """One bandit round: Eq. 5 softmax rewards, Eq. 8 loss re-calibration.

    ``rewards`` lets the fused ``gp_projection_softmax`` kernel hand its
    already-normalised c̃ straight to the GPCB update (flat layout +
    ``score_kernel``); ``None`` computes the softmax here."""
    with jax.named_scope("gpfl_gate"):
        if rewards is None:
            rewards = gp.normalize_gp(scores)
        mu = rewards * mask
        mu_cal = gpcb.calibrate_reward(mu, bandit.prev_acc, bandit.prev_acc,
                                       loss_scalar, bandit.prev_loss)
        new_bandit = gpcb.update_state(bandit, mask, mu_cal,
                                       bandit.prev_acc, loss_scalar)
    return new_bandit, mu_cal


def _aux_mean(auxes):
    return jax.tree.map(lambda *xs: jnp.mean(jnp.stack(xs)), *auxes)


def make_plain_train_step(api, *, lr, gamma: float = 0.9,
                          weight_decay: float = 0.0, rules=None,
                          remat: str = "full", grad_specs=None,
                          unroll: bool = False, ce_chunks: int = 0):
    """Ungated baseline step: full-batch ``value_and_grad`` + MGD (Eq. 1-2).

    ``(state, batch) → (state, metrics)`` over the same :class:`TrainState`
    as the GPFL step (the bandit rides along untouched), so the two are
    drop-in interchangeable in the launch scripts.  ``grad_specs`` (a
    PartitionSpec tree matching ``params``) pins the gradient sharding on a
    mesh; ``None`` on CPU.
    """
    lkw = _loss_kwargs(rules, remat, unroll, ce_chunks)

    def loss(p, b):
        return api.loss_fn(p, b, **lkw)

    def step(state: TrainState, batch):
        with jax.named_scope("step_grad"):
            (loss_val, aux), grads = jax.value_and_grad(loss, has_aux=True)(
                state.params, batch)
        grads = _constrain(grads, grad_specs)
        with jax.named_scope("step_update"):
            new_params, mstate = mgd_update(
                state.params, grads, MGDState(state.momentum, state.step),
                lr=lr, gamma=gamma, weight_decay=weight_decay)
        loss32 = loss_val.astype(jnp.float32)
        new_state = TrainState(new_params, mstate.momentum, state.bandit,
                               state.step + 1, loss32)
        return new_state, {"loss": loss_val, **aux}

    return step


def make_gpfl_train_step(api, *, n_groups: int, k_select: int,
                         total_rounds: int, lr, gamma: float = 0.9,
                         rho: float = 1.0, weight_decay: float = 0.0,
                         impl: str = "jvp", gate: bool = True, rules=None,
                         remat: str = "full", grad_specs=None,
                         unroll: bool = False, ce_chunks: int = 0,
                         score_kernel: bool = False,
                         param_layout: str = "tree"):
    """Build the jit-friendly GPFL round: ``(state, batch) → (state, metrics)``.

    Args:
      api: a ``repro.models.ModelApi``.
      n_groups: virtual clients per step; must divide the batch size.
      k_select: groups admitted into the MGD update each round.
      total_rounds: T of the Eq. 7 exploration ramp ``α = ρ·t/T``.
      lr, gamma, weight_decay: MGD hyper-parameters (Eq. 1-2).
      rho: exploration weight scale (Eq. 7).
      impl: ``"jvp"`` (one forward-mode pass for all scores, no per-group
        gradient materialisation) or ``"grads"`` (K backward passes, stacked
        grads).  See the module doc for the equivalence argument.
      gate: ``False`` → compute scores/bandit for observability but apply the
        plain full-batch update (bit-identical to
        :func:`make_plain_train_step`).
      rules / remat / unroll / ce_chunks: forwarded to the model's loss.
      grad_specs: PartitionSpec tree to pin gradient sharding on a mesh.
      score_kernel: route the grads-impl projection through the Pallas
        kernels (interpret-mode on CPU) — in the flat layout this is the
        fused ``gp_projection_softmax``, whose Eq. 5 rewards feed the
        GPCB update directly.
      param_layout: gradient-workspace layout for the grads impl.
        ``"flat"`` packs the per-group gradients through one
        ``repro.core.flat.FlatSpec`` into a contiguous (K, D) matrix —
        the projection is one matvec, the gated aggregate is one
        weighted row-combine, and the layout is the same contiguous
        wire format a cross-host all-reduce would ship (one vector op
        instead of a per-leaf walk).  The jvp impl never materialises
        gradients, so the switch is a no-op there.

    Returned metrics: ``loss``, ``ce`` (+ model aux), ``gp_scores`` (K,),
    ``selected_mask`` (K, float 0/1), ``reward`` (K, calibrated μ) and
    ``gpcb_values`` (K, +inf for never-selected groups).
    """
    if impl not in ("jvp", "grads"):
        raise ValueError(f"impl must be 'jvp' or 'grads', got {impl!r}")
    if param_layout not in ("tree", "flat"):
        raise ValueError(f"param_layout must be 'tree' or 'flat'; "
                         f"got {param_layout!r}")
    if not 1 <= k_select <= n_groups:
        raise ValueError(f"k_select={k_select} outside [1, {n_groups}]")
    is_flat = param_layout == "flat"
    lkw = _loss_kwargs(rules, remat, unroll, ce_chunks)

    def loss(p, b):
        return api.loss_fn(p, b, **lkw)

    def scores_and_losses_jvp(params, momentum, gbs):
        """All K scores from ONE forward-mode pass along the momentum."""

        def per_group(p):
            outs = [loss(p, b) for b in gbs]
            return jnp.stack([o[0] for o in outs]), [o[1] for o in outs]

        tangent = jax.tree.map(lambda m, pp: m.astype(pp.dtype),
                               momentum, params)
        (losses, auxes), (l_tan, _) = jax.jvp(per_group, (params,),
                                              (tangent,))
        dn = tree_global_norm(momentum)
        scores = l_tan / jnp.maximum(dn, 1e-12)
        return scores, losses, auxes, None, None

    def scores_and_losses_grads(params, momentum, gbs):
        """All K scores from K materialised per-group gradients.

        Flat layout: the gradients land in one contiguous (K, D)
        ``FlatSpec`` workspace — the projection is a single matvec (or
        the fused softmax kernel) and the matrix doubles as the gated
        update's aggregation (and all-reduce) buffer."""
        results = [jax.value_and_grad(loss, has_aux=True)(params, b)
                   for b in gbs]
        losses = jnp.stack([r[0][0] for r in results])
        auxes = [r[0][1] for r in results]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[r[1] for r in results])
        rewards = None
        if is_flat:
            spec = flat_mod.make_flat_spec(params)
            gmat = flat_mod.pack_stacked(spec, stacked)
            dvec = flat_mod.pack(spec, momentum)
            if score_kernel:
                from repro.kernels.ops import gp_projection_softmax
                scores, rewards = gp_projection_softmax(gmat, dvec)
            else:
                scores = gp.gp_scores_matrix(gmat, dvec)
            return scores, losses, auxes, (spec, gmat), rewards
        if score_kernel:
            from repro.kernels.ops import gp_projection_tree
            scores = gp_projection_tree(stacked, momentum)
        else:
            scores = gp.gp_scores_stacked(stacked, momentum)
        return scores, losses, auxes, stacked, rewards

    score_fn = scores_and_losses_jvp if impl == "jvp" \
        else scores_and_losses_grads

    def step(state: TrainState, batch):
        params, momentum = state.params, state.momentum
        gbs = _group_batches(batch, n_groups)
        with jax.named_scope("gpfl_score"):
            scores, losses, auxes, stacked, rewards = score_fn(
                params, momentum, gbs)
        scores = jax.lax.stop_gradient(scores)

        if gate:
            mask, u = _select(state.bandit, scores, k_select, total_rounds,
                              rho)
            loss_scalar = jnp.mean(losses)
            aux = _aux_mean(auxes)
            with jax.named_scope("step_grad"):
                if isinstance(stacked, tuple):  # flat workspace: one row-combine
                    spec, gmat = stacked
                    w = mask / jnp.maximum(mask.sum(), 1.0)
                    grads = flat_mod.unpack(spec,
                                            jnp.tensordot(w, gmat, axes=1))
                elif stacked is not None:  # tree grads impl: mask-average leaves
                    w = mask / jnp.maximum(mask.sum(), 1.0)
                    grads = jax.tree.map(
                        lambda s: jnp.tensordot(
                            w, s.astype(jnp.float32), axes=1).astype(
                                s.dtype),
                        stacked)
                else:  # jvp impl: one backward pass of the mask-weighted loss
                    def masked_loss(p):
                        lvec = jnp.stack([loss(p, b)[0] for b in gbs])
                        return (mask * lvec).sum() / jnp.maximum(
                            mask.sum(), 1.0)

                    grads = jax.grad(masked_loss)(params)
        else:
            # bit-exact plain path: the would-be selection is still computed
            # and recorded (metrics + bandit) so an ungated run logs what
            # GPFL would have picked, but the update uses the full batch.
            mask, u = _select(state.bandit, scores, k_select, total_rounds,
                              rho)
            with jax.named_scope("step_grad"):
                (loss_scalar, aux), grads = jax.value_and_grad(
                    loss, has_aux=True)(params, batch)

        grads = _constrain(grads, grad_specs)
        new_bandit, mu_cal = _observe(state.bandit, mask, scores,
                                      jnp.mean(losses), rewards)
        with jax.named_scope("step_update"):
            new_params, mstate = mgd_update(
                params, grads, MGDState(momentum, state.step),
                lr=lr, gamma=gamma, weight_decay=weight_decay)
        new_state = TrainState(new_params, mstate.momentum, new_bandit,
                               state.step + 1,
                               loss_scalar.astype(jnp.float32))
        metrics = {"loss": loss_scalar, **aux, "gp_scores": scores,
                   "selected_mask": mask, "reward": mu_cal,
                   "gpcb_values": u}
        return new_state, metrics

    return step


def make_gpfl_apply_step(api, *, n_groups: int, k_select: int,
                         total_rounds: int, lr, gamma: float = 0.9,
                         rho: float = 1.0, weight_decay: float = 0.0,
                         rules=None, remat: str = "full", grad_specs=None,
                         unroll: bool = False, ce_chunks: int = 0):
    """Amortised GPFL: apply the bandit's CURRENT selection without re-scoring.

    Re-deriving the top-k from the carried ``BanditState`` is free (counts
    and reward sums only change when a scored step observes a round), so a
    ``--score-every N`` schedule runs one :func:`make_gpfl_train_step` round
    followed by N-1 of these — each saving the score pass (the jvp forward
    sweep or the K-1 extra backward passes) while still training only on
    bandit-approved groups.  Selection here is PURE EXPLOITATION: top-k of
    the GPCB values over arms the bandit has actually observed, with
    never-selected arms ranked last — an apply step gathers no evidence, so
    spending the must-explore rule on unobserved arms would train on
    never-approved groups and record nothing.  Exploration happens on the
    scored rounds.  The bandit itself is left untouched: no new evidence was
    gathered, so no round is recorded.
    """
    if not 1 <= k_select <= n_groups:
        raise ValueError(f"k_select={k_select} outside [1, {n_groups}]")
    lkw = _loss_kwargs(rules, remat, unroll, ce_chunks)

    def loss(p, b):
        return api.loss_fn(p, b, **lkw)

    def step(state: TrainState, batch):
        params = state.params
        gbs = _group_batches(batch, n_groups)
        mask, u = _select(state.bandit, jnp.zeros((n_groups,), jnp.float32),
                          k_select, total_rounds, rho, explore_unseen=False)

        def masked_loss(p):
            outs = [loss(p, b) for b in gbs]
            lvec = jnp.stack([o[0] for o in outs])
            tot = (mask * lvec).sum() / jnp.maximum(mask.sum(), 1.0)
            return tot, (lvec, [o[1] for o in outs])

        with jax.named_scope("step_grad"):
            (_, (losses, auxes)), grads = jax.value_and_grad(
                masked_loss, has_aux=True)(params)
        grads = _constrain(grads, grad_specs)
        with jax.named_scope("step_update"):
            new_params, mstate = mgd_update(
                params, grads, MGDState(state.momentum, state.step),
                lr=lr, gamma=gamma, weight_decay=weight_decay)
        loss_scalar = jnp.mean(losses)
        new_state = TrainState(new_params, mstate.momentum, state.bandit,
                               state.step + 1,
                               loss_scalar.astype(jnp.float32))
        metrics = {"loss": loss_scalar, **_aux_mean(auxes),
                   "gp_scores": jnp.zeros((n_groups,), jnp.float32),
                   "selected_mask": mask, "gpcb_values": u}
        return new_state, metrics

    return step
