"""End-to-end FL simulation (paper Algorithm 1 + all baselines).

One ``run_experiment(FLExperimentConfig)`` call reproduces one cell of the
paper's Table II: build the synthetic dataset, partition it (1SPC/2SPC/Dir),
run T rounds of select → local-train (vmapped cohort) → FedAvg → evaluate,
and return the full metric history (accuracy curve, selection log, wall time).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.api.capabilities import support_matrix
from repro.configs.paper import FLExperimentConfig
from repro.core import gp as gp_mod
from repro.core.selector import RoundFeedback, make_selector, PowDSelector
from repro.data import ClientStore, make_dataset, partition
from repro.fl.client import make_cohort_trainer, make_cohort_loss_eval
from repro.fl.server import fedavg, make_evaluator, update_global_direction
from repro.models import small


#: Which knob works where — DERIVED from the capability registry
#: (``repro.api.capabilities.CAPABILITIES``), the same rows that drive
#: the fail-fast validation, so this string can never drift from what
#: actually runs.  Embedded verbatim in every compatibility error.
SUPPORT_MATRIX = support_matrix()


@dataclasses.dataclass
class RunResult:
    """The full history of one FL experiment (either backend).

    Attributes:
        config: the experiment that produced this result.
        accuracy: (T,) global test accuracy per round.
        loss: (T,) global test loss per round.
        selections: (T, K) selected client ids per round.
        round_time_s: (T,) wall seconds per round (the scan backend
            reports the amortised time of its single dispatch).
        selection_counts: (N,) times each client was selected.
        coverage: (T,) fraction of clients seen at least once.
        sim_time_s: buffered-aggregation runs only — (E,) simulated
            server clock at each aggregation event (when the M-th
            in-flight update landed, in latency-model seconds); the
            x-axis of time-to-accuracy comparisons.  ``None`` for sync
            runs, whose per-row histories are indexed by round.
        pools: pooled pre-selection runs only — (T, P) tier-1 candidate
            pool ids per round (ascending), the oracle-parity harness's
            subset witness.  ``None`` for full-population runs.
        metrics: telemetry runs only (``telemetry="counters"|"trace"``) —
            per-step counter arrays keyed by name (participants,
            delivered, bytes_up/bytes_down, selection_entropy,
            gp_alignment, screened, quarantined, pool_recall, and — for
            buffered runs — the (E, B) staleness histogram); see
            ``repro.obs.metrics``.  ``None`` for ``telemetry="off"``.
    """
    config: FLExperimentConfig
    accuracy: np.ndarray          # (T,)
    loss: np.ndarray              # (T,)
    selections: np.ndarray        # (T, K)
    round_time_s: np.ndarray      # (T,)
    selection_counts: np.ndarray  # (N,)
    coverage: np.ndarray          # (T,) fraction of clients seen ≥1×
    sim_time_s: Optional[np.ndarray] = None  # (E,) buffered event clock
    pools: Optional[np.ndarray] = None       # (T, P) tier-1 pool ids
    metrics: Optional[Dict[str, np.ndarray]] = None  # telemetry counters

    def final_accuracy(self, last: int = 10) -> float:
        """Mean accuracy over the final ``last`` rounds (Table II style)."""
        return float(self.accuracy[-last:].mean())

    def accuracy_at(self, frac: float) -> float:
        """Accuracy at a fraction of the round budget (Fig. 4 x-axis)."""
        i = max(0, int(len(self.accuracy) * frac) - 1)
        return float(self.accuracy[i])


def _build_data(exp: FLExperimentConfig, seed: int,
                host_tables: bool = False):
    """Synthesize + partition the experiment's dataset.

    Returns ``(ClientStore, eval_x, eval_y)`` — deterministic in
    ``seed``, shared by both backends so they train on identical bytes.
    ``host_tables=True`` keeps the client tables host-resident (the
    streamed pooled runner's large-population mode).
    """
    total = exp.n_clients * exp.samples_per_client_mean
    data = make_dataset(exp.model.name, total + exp.eval_size, seed=seed)
    train_x, train_y = data.x[: total], data.y[: total]
    eval_x, eval_y = data.x[total :], data.y[total :]
    from repro.data.synthetic import Dataset
    train = Dataset(x=train_x, y=train_y, num_classes=data.num_classes)
    parts = partition(exp.partition, train_y, exp.n_clients,
                      zeta=exp.dirichlet_zeta, seed=seed)
    store = ClientStore(train, parts, host_tables=host_tables)
    return store, jnp.asarray(eval_x), jnp.asarray(eval_y)


#: init-phase chunk size (peak-memory knob).  The chunking — and the
#: per-chunk ``fold_in`` offsets — must be identical everywhere the init
#: phase runs (host loop, scan engine, batched multi-seed engine) or the
#: seed GPs (and hence round-0 selections) diverge; every caller shares
#: this constant.
INIT_CHUNK = 25


def resolve_gp_impl(gp_impl: str, use_gp_kernel: bool) -> str:
    """The GP scoring implementation a run uses: ``use_gp_kernel`` forces
    the Pallas kernel; ``"auto"`` picks it on a TPU backend and the jnp
    einsum elsewhere.  Both backends resolve through here, so the host
    loop scores with the same code as the scan on a given device."""
    if use_gp_kernel:
        return "kernel"
    if gp_impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "stacked"
    if gp_impl not in ("kernel", "stacked"):
        raise ValueError(f"gp_impl must be 'auto', 'kernel' or 'stacked'; "
                         f"got {gp_impl!r}")
    return gp_impl


def init_gp_phase(trainer, store, params, kinit, *, chunk: int = INIT_CHUNK):
    """Algorithm 1's initialization phase: every client trains once from
    w^0 (in chunks, bounding peak memory) → the seed global direction and
    the seed GP score of every client.

    Shared verbatim by the host loop and the compiled engine
    (``repro.fl.engine``) so both backends start from bit-identical seed
    GPs — round-0 selection is a deterministic top-K of these."""
    N = store.n_clients
    all_momenta = []
    for ofs in range(0, N, chunk):
        ids = np.arange(ofs, min(ofs + chunk, N))
        x, y, sizes = store.gather(ids)
        rngs = jax.random.split(jax.random.fold_in(kinit, ofs), len(ids))
        _, d_i, _ = trainer(params, x, y, sizes, rngs)
        all_momenta.append(d_i)
    momenta = jax.tree.map(lambda *xs: jnp.concatenate(xs), *all_momenta)
    direction = jax.tree.map(lambda m: jnp.mean(m, axis=0), momenta)
    gp_all = gp_mod.gp_scores_stacked(momenta, direction)
    return direction, gp_all


def run_experiment(exp: FLExperimentConfig, *, log_every: int = 0,
                   use_gp_kernel: bool = False, backend: str = "python",
                   param_layout: str = "tree", scenario="full",
                   aggregation="sync", buffer_size: Optional[int] = None,
                   staleness_discount: Optional[float] = None,
                   shard_clients: int = 1) -> RunResult:
    """Run one FL experiment — a thin shim over a one-cell declarative
    Plan (``repro.api``), kept for the legacy kwarg surface.

    .. deprecated:: the kwarg pile is frozen — new execution knobs land
       on :class:`repro.api.ExecutionSpec` only (this shim routes every
       call through ``repro.api.spec_from_kwargs``, so prefer building
       the spec directly: ``Plan(exp).execute_with(ExecutionSpec(...))``).

    The kwargs map 1:1 onto a ``repro.api.ExecutionSpec``; the actual
    dispatch (backend choice, validation against the capability
    registry, dataset build) happens in ``repro.api.Session`` exactly as
    it would for a multi-cell sweep, so ``run_experiment(exp, ...)`` and
    a one-cell ``Plan(exp).execute_with(spec).run()`` are the same code
    path (pinned by ``tests/test_api.py``).

    Args:
        exp: the experiment config (one cell of the paper's Table II).
        log_every: print progress every N rounds (0 = silent).
        use_gp_kernel: route GP scoring through the Pallas kernel.
        backend: ``"python"`` (reference host loop,
            :func:`run_python_loop`) or ``"scan"`` (the compiled round
            engine, ``repro.fl.engine``).
        param_layout: scan-backend carry layout (``"tree"`` | ``"flat"``).
        scenario: heterogeneity scenario (scan backend only) —
            ``"full"``, ``"availability"``, ``"stragglers"`` or a
            ``repro.fl.latency.ScenarioConfig``.
        aggregation: ``"sync"`` (the paper's blocking rounds),
            ``"buffered"`` (FedBuff-style event scan, scan backend only)
            or a ``repro.fl.latency.AggregationConfig``.
        buffer_size: buffered-mode buffer M (``None`` keeps the config
            default; rejected with ``aggregation="sync"``).
        staleness_discount: buffered-mode staleness weight base
            (likewise).
        shard_clients: shard the cohort over this many devices on a
            ``("clients",)`` mesh (scan backend, flat layout only).

    Returns:
        The :class:`RunResult` history.

    Raises:
        ValueError: an unsupported combination — raised BEFORE anything
            compiles, with the registry-derived :data:`SUPPORT_MATRIX`
            in the message.
    """
    from repro.api import Plan, spec_from_kwargs
    spec = spec_from_kwargs(backend=backend, param_layout=param_layout,
                            scenario=scenario, shard_clients=shard_clients,
                            use_gp_kernel=use_gp_kernel,
                            aggregation=aggregation, buffer_size=buffer_size,
                            staleness_discount=staleness_discount)
    runset = Plan(exp).execute_with(spec, log_every=log_every).run()
    if not runset.runs and runset.failures:
        # a one-cell run has no sweep to degrade gracefully for: surface
        # the original error instead of an empty RunSet
        failure = runset.failures[0]
        if failure.exception is not None:
            raise failure.exception
        raise RuntimeError(failure.error)
    return runset[0]


def run_python_loop(exp: FLExperimentConfig, *, log_every: int = 0,
                    use_gp_kernel: bool = False, data=None) -> RunResult:
    """The reference host round loop (``backend="python"``).

    One round at a time: numpy selector → device gather → jitted cohort
    train → host-synced eval → numpy bandit update.  The parity oracle
    every compiled path must replay bit-identically.

    Args:
        exp: the experiment config.
        log_every: print progress every N rounds (0 = silent).
        use_gp_kernel: route GP scoring through the Pallas kernel (it
            is used on a TPU backend regardless, as by the scan engine).
        data: optional prebuilt ``(store, eval_x, eval_y)`` (a Session's
            dataset cache); ``None`` builds from ``exp``.

    Returns:
        The :class:`RunResult` history.
    """
    rng_np = np.random.default_rng(exp.seed)
    key = jax.random.key(exp.seed)

    store, eval_x, eval_y = data if data is not None \
        else _build_data(exp, exp.seed)
    key, k0 = jax.random.split(key)
    params = small.init(k0, exp.model)

    trainer = make_cohort_trainer(exp)
    loss_eval = make_cohort_loss_eval(exp)
    evaluate = make_evaluator(exp, eval_x, eval_y)
    selector = make_selector(exp.selector, store.n_clients,
                             exp.clients_per_round, exp.rounds, rho=exp.rho,
                             warmup=exp.fedcor_warmup, d=exp.powd_d)

    N, K, T = store.n_clients, exp.clients_per_round, exp.rounds
    use_kernel = resolve_gp_impl("auto", use_gp_kernel) == "kernel"
    direction = None

    # ---- initialization phase (Algorithm 1): every client trains once ----
    if hasattr(selector, "seed_gp"):
        key, kinit = jax.random.split(key)
        direction, gp_all = init_gp_phase(trainer, store, params, kinit)
        selector.seed_gp(np.asarray(gp_all))

    acc_hist, loss_hist, sel_hist, time_hist = [], [], [], []
    counts = np.zeros(N, np.int64)
    coverage = []
    seen = np.zeros(N, bool)

    for t in range(T):
        t0 = time.perf_counter()

        # ---- selection (pre- or post- style per selector) ----
        if isinstance(selector, PowDSelector):
            cands = selector.propose_candidates(rng_np)
            x, y, sizes = store.gather(cands)
            cand_losses = loss_eval(params, x, y, sizes)
            selector.receive_candidate_losses(np.asarray(cand_losses))
        all_losses = None
        if getattr(selector, "needs_all_losses", False):
            x, y, sizes = store.gather(np.arange(N))
            all_losses = np.asarray(loss_eval(params, x, y, sizes))
        ids = np.asarray(selector.select(rng_np, t))

        # ---- cohort local training (one compiled vmap) ----
        x, y, sizes = store.gather(ids)
        key, kt = jax.random.split(key)
        rngs = jax.random.split(kt, len(ids))
        w_i, d_i, local_losses = trainer(params, x, y, sizes, rngs)

        # ---- GP scores vs the global momentum direction (Eq. 3) ----
        if direction is not None:
            if use_kernel:
                from repro.kernels.ops import gp_projection_tree
                gp_scores = gp_projection_tree(d_i, direction)
            else:
                gp_scores = gp_mod.gp_scores_stacked(d_i, direction)
            gp_scores = np.asarray(gp_scores)
        else:
            gp_scores = np.zeros(len(ids), np.float32)

        # ---- FedAvg + global direction update ----
        w_prev = params
        params = fedavg(w_i)
        direction = update_global_direction(direction, w_prev, params,
                                            exp.lr, exp.momentum)

        # ---- evaluate + bandit feedback ----
        acc, gl_loss = evaluate(params)
        acc, gl_loss = float(acc), float(gl_loss)
        selector.observe(RoundFeedback(
            round_idx=t, selected=ids, gp_scores=gp_scores,
            global_acc=acc, global_loss=gl_loss, client_losses=all_losses))

        counts[ids] += 1
        seen[ids] = True
        acc_hist.append(acc)
        loss_hist.append(gl_loss)
        sel_hist.append(ids)
        coverage.append(seen.mean())
        time_hist.append(time.perf_counter() - t0)
        if log_every and (t + 1) % log_every == 0:
            print(f"[{exp.name}] round {t+1}/{T} acc={acc:.4f} "
                  f"loss={gl_loss:.4f} cov={seen.mean():.2f}")

    return RunResult(
        config=exp,
        accuracy=np.asarray(acc_hist, np.float32),
        loss=np.asarray(loss_hist, np.float32),
        selections=np.asarray(sel_hist),
        round_time_s=np.asarray(time_hist, np.float32),
        selection_counts=counts,
        coverage=np.asarray(coverage, np.float32),
    )
