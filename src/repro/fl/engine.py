"""The compiled round engine: T federated rounds in ONE jitted ``lax.scan``.

``run_experiment(..., backend="python")`` dispatches one host round at a
time: numpy selector → device gather → jitted cohort train → host-synced
eval → numpy bandit update.  That is 5+ host/device crossings per round,
so on the paper-scale models round time is dispatch-dominated — exactly
the per-round burden GPFL's pre-selection is supposed to remove.

This module keeps the whole simulation device-resident.  Each scan step
fuses the full round:

    selection (any of the paper's four selectors, pure-jnp)
      → cohort gather from the ClientStore's device tables
      → vmapped local training (Eq. 1-2), optionally client-sharded
      → GP scoring against the global direction (Eq. 3)
      → FedAvg + momentum-direction update
      → evaluation
      → bandit / GP-posterior update (carried state).

Selectors (the engine is selector-agnostic; ``ENGINE_SELECTORS`` lists
all four of the paper's policies):

* ``gpfl`` — pure-jnp GPCB ranking (``repro.core.gpcb.selection_scores``)
  with the host RNG's tie-break jitter precomputed into a (T, N) scan
  input (``repro.core.selector.gpfl_jitter_stream``).
* ``random`` — the host RNG's K-of-N draws precomputed into a (T, K)
  scan input (``random_id_stream``), so the scan replays the host loop's
  cohorts bit-identically (PR 2's jax-PRNG permutation is gone).
* ``powd`` — candidate pools precomputed from the host RNG
  (``powd_candidate_stream``); the d-candidate loss probe and the
  highest-loss top-K ranking run in-scan against the current params.
* ``fedcor`` — warm-up cohorts precomputed (``fedcor_warmup_stream``);
  the all-client loss probe, the covariance EMA and the greedy
  GP-posterior pick (``fedcor_cov_update`` / ``fedcor_greedy``) run
  in-scan, carried as (N, N) / (N,) scan state.  The host selector calls
  the SAME jnp functions, so the two backends share one implementation.

Parity contract (pinned by ``tests/test_engine.py`` and
``tests/test_selectors_scan.py``): for every selector the engine replays
the host loop's selection history — both backends share the
initialization phase (``simulation.init_gp_phase``), the identical
per-round key-split sequence, and per-selector host-RNG streams
precomputed into scan inputs.  (The engine ranks in float32 where parts
of the host path rank through numpy; jitter-scale near-ties can in
principle order differently, but the score gaps between distinct clients
are far wider than the tie-break noise.)

Parameter layouts (``param_layout``):

* ``"tree"`` (default, the parity oracle) — the carry holds parameter
  pytrees and the server side walks the leaves: FedAvg mean, direction
  axpy and GP einsum per leaf, dozens of small ops per scanned round.
* ``"flat"`` — the engine builds a ``repro.core.flat.FlatSpec`` once at
  construction and the carry holds ONE padded ``(Dp,)`` float32 vector
  for params and one for the direction.  The cohort's trained params /
  momenta are packed into ``(K, Dp)`` matrices right out of the trainer,
  the whole server update is ``server_update_flat`` (two contiguous
  vector passes, or the fused Pallas ``fedavg_momentum`` kernel when the
  kernels compile for real), and GP scores feed ``gp_projection`` /
  ``gp_scores_matrix`` directly — no per-round re-flatten.

Client-sharded cohorts (``shard_clients > 1``, flat layout only): the
engine builds a 1-D ``("clients",)`` mesh (layout rules from
``repro.dist.sharding.cohort_axis_rules`` — same logical-axis→mesh-axis
convention as ``arch_rules``) and wraps the cohort step in
``jax.shard_map``: each device trains K/n of the round's clients, packs
its own ``(K/n, Dp)`` slab and computes its clients' GP projections
locally.  The slabs and scores are then ``all_gather``-ed (tiled, so row
order matches the single-device layout exactly) and the O(K·Dp) server
reduction runs on the gathered replicas — the bit-parity contract pins
the FedAvg reduction order, so the reduction is NOT re-sharded (it is
negligible next to local training, which is where the devices pay).
``tests/test_shard_cohort.py`` pins 2-device selections bit-identical to
the single-device scan.

Heterogeneity scenarios (``scenario=``, see
``repro.fl.latency.ScenarioConfig``): per-round client availability
masks restrict every selector to the round's reachable clients;
straggler deadlines drop late clients from FedAvg and from GPFL's bandit
feedback (their completion times come from ``fl.latency.LatencyModel``).
Both ride into the scan as precomputed (T, N) inputs — no host round
trips.

Buffered asynchronous aggregation (``aggregation="buffered"``, see
``repro.fl.latency.AggregationConfig``): the scan iterates over
aggregation *events* instead of rounds (FedBuff).  A pool of K clients
stays in flight at completion times drawn from the scenario's latency
model; each event flushes the M = ``buffer_size`` earliest-completing
updates with staleness-discounted FedAvg weights (λ ∝ discount^s for an
update trained s events ago), advances the simulated clock to the M-th
completion, gates STALE updates out of GPFL's bandit feedback
(``gpcb.observe(valid_mask=)``) and dispatches M replacement clients
selected against the just-aggregated model.  One jitted dispatch still
covers the whole run (the prefill prologue — sync round 0's cohort going
into the pool — plus all E events), both param layouts.  Parity
contract: ``staleness_discount=1.0`` + a zero-latency model + M = K
replays the sync engine bit-identically — an all-fresh buffer takes the
sync engine's ``weights=None`` reduction, the stable ready-time argsort
preserves dispatch order, and event e consumes stream row e+1 (row 0 is
the prefill's), so the selector streams' first T rows are consumed
exactly as the sync scan consumes them.  CI gates this via the async
bench (``BENCH_async.json``).  Snapshots/resume work unchanged —
``snapshot_every``/``until_round`` count events.

GP score path: ``gp_impl="auto"`` routes through the Pallas kernels
wherever they compile for real (TPU) and through jnp elsewhere —
interpret mode is resolved per-backend by ``repro.kernels.interpret``,
never hard-coded.  In flat layout the kernel route also engages the
fused ``fedavg_momentum`` server kernel.  (Client-sharded runs score GP
with the jnp matrix path inside ``shard_map``.)

The jitted scan donates the params/direction carry buffers
(``donate_argnums``): XLA aliases them into the scan's carry in place of
keeping a second resident copy alive for the caller.  ``run()`` hands the
scan fresh ``jnp.copy`` buffers so the engine stays re-runnable (and the
cached initial state stays pristine); on backends without donation
support (CPU) XLA silently falls back to a copy.

Fault tolerance (``snapshot_every > 0``): the single T-round scan is
segmented into chunked scans of N rounds sharing the SAME jitted round
body, so the composition replays the unsegmented run's selection history
and final params bit-identically (chunk boundaries only change where the
host syncs, never the per-round math; pinned by ``tests/test_resume.py``
for all four selectors and both layouts).  After every chunk the full
``RoundCarry`` — plus the metric history so far — is written to
``snapshot_path`` via ``repro.checkpoint.msgpack_ckpt`` (atomic rename,
config-fingerprint meta).  The chunked dispatch donates the whole carry;
the snapshot ``jax.device_get``s it to host FIRST, so the saved bytes
are never aliased by the next chunk (donated-buffer-safe).
``run(resume=True)`` restores the newest snapshot and finishes the
remaining rounds; ``run(until_round=k)`` stops (and snapshots) at round
k, which is how a budgeted/preempted run hands off to a later resume.

Batched multi-seed dispatch (``BatchedSeedEngine`` /
``run_batched_seeds``): the round-scan takes the client tables and the
eval set as runtime ARGUMENTS, so S runs differing only in seed vmap
over one leading seed axis — one trace, one compile, one device dispatch
for all S seeds, with per-seed selection histories bit-identical to S
sequential runs.  This is what a ``repro.api.Session`` dispatches for
``Plan(...).seeds(S)`` sweeps; ``benchmarks.run --only sweep`` records
the batched-vs-sequential throughput (``BENCH_sweep.json``).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, \
    Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.api.capabilities import PARAM_LAYOUTS, SELECTORS, SpecView
from repro.api.capabilities import validate as validate_capabilities
from repro.checkpoint.msgpack_ckpt import (peek_meta, restore_checkpoint,
                                           save_checkpoint)
from repro.configs.paper import FLExperimentConfig
from repro.core import flat as flat_mod
from repro.core import gp as gp_mod
from repro.core import gpcb
from repro.core.selector import (fedcor_cov_update, fedcor_greedy,
                                 fedcor_warmup_stream, gpfl_jitter_stream,
                                 pool_jitter_stream, pool_rank_stream,
                                 powd_candidate_stream, powd_default_d,
                                 random_id_stream)
from repro.data import ClientStore
from repro.dist.sharding import (cohort_axis_rules, cohort_specs,
                                 population_axis_rules)
from repro.fl.client import (make_cohort_loss_eval, make_cohort_trainer,
                              minibatch_path)
from repro.fl.faults import (FaultConfig, corrupt_cohort, fault_stream,
                             make_faults)
from repro.fl.latency import (AggregationConfig, ScenarioConfig,
                              availability_stream, completion_time_stream,
                              make_aggregation, make_scenario)
from repro.fl.preselect import (PreselectConfig, compose_selection_mask,
                                make_preselect, run_pooled_stream)
from repro.fl.robust import (RobustConfig, finite_rows, make_robust,
                             robust_aggregate)
from repro.fl.server import (fedavg, make_table_evaluator, server_update_flat,
                             update_global_direction,
                             update_global_direction_flat)
from repro.fl.simulation import (INIT_CHUNK, RunResult, _build_data,
                                 init_gp_phase, resolve_gp_impl)
from repro.models import small
from repro.obs import metrics as obs_metrics
from repro.obs.cost import BYTES_PER_PARAM, padded_param_count
from repro.obs.trace import NullTracer, SpanTracer
from repro.utils.pytree import tree_zeros_like

#: selectors the compiled engine supports — all four of the paper's
#: policies (host-RNG streams precomputed, state-dependent decisions
#: re-derived in-scan; see the module doc).  Aliased from the capability
#: registry (as is ``PARAM_LAYOUTS``, re-exported above) so the engine
#: and the derived support matrix cannot drift.
ENGINE_SELECTORS = SELECTORS

#: FedCor's covariance EMA discount (matches FedCorSelector's default).
_FEDCOR_BETA = 0.95

#: the spans of an engine built without ``telemetry="trace"``
_NULL_TRACER = NullTracer()


class RoundCarry(NamedTuple):
    """Device-resident state carried across scanned rounds (or, in the
    buffered aggregation backend, across aggregation *events*).

    ``params`` / ``direction`` are parameter pytrees in the tree layout
    and padded ``(Dp,)`` workspace vectors in the flat layout.
    ``fc_cov`` / ``fc_prev`` hold FedCor's (N, N) client covariance and
    previous all-client loss vector ((1, 1)/(1,) placeholders for the
    other selectors, so the carry stays cheap).  The ``pool_*`` fields
    are the buffered backend's in-flight client pool — K trained-but-not-
    yet-aggregated updates with their owner ids, completion times and
    the model version each trained against (tiny placeholders in sync
    mode, like ``fc_cov``)."""
    params: Any               # global model w^t
    direction: Any            # global momentum direction g (Eq. 1-2)
    bandit: gpcb.BanditState  # reward sums / selection counts / round
    latest_gp: jnp.ndarray    # (N,) persistent C vector (Algorithm 1)
    seen: jnp.ndarray         # (N,) bool — coverage tracking
    key: jnp.ndarray          # PRNG key, split once per round
    fc_cov: jnp.ndarray       # (N, N) FedCor covariance EMA
    fc_prev: jnp.ndarray      # (N,) FedCor previous loss probe
    pool_w: Any               # (K, ...) in-flight trained params (buffered)
    pool_d: Any               # (K, ...) in-flight local momenta (buffered)
    pool_ids: jnp.ndarray     # (K,) i32 owner client of each slot
    pool_ready: jnp.ndarray   # (K,) f32 completion time of each slot
    pool_ver: jnp.ndarray     # (K,) i32 model version each slot trained on
    clock: jnp.ndarray        # () f32 simulated server time
    pool_ok: jnp.ndarray      # (K,) bool delivery mask of each slot
    #: (N,) i32 per-client corruption strike counts, driving the
    #: ``quarantine_after`` selection mask ((1,) stub when quarantine off)
    strikes: jnp.ndarray
    #: (N,) f32 round each client was last selected (−1 = never), feeding
    #: the tier-1 pool recency term ((1,) stub when pre-selection is off)
    last_sel: jnp.ndarray
    #: (N,) i32 cumulative per-client selection tally, feeding the
    #: selection-entropy counter ((1,) stub when telemetry is off)
    sel_counts: jnp.ndarray


def _copy_carry(c: RoundCarry) -> RoundCarry:
    """A fresh-buffer deep copy of a carry (safe to donate).  PRNG keys
    are copied through their raw key data (extended dtypes have no
    ``jnp.copy``)."""
    cp = functools.partial(jax.tree.map, jnp.copy)
    d = c._asdict()
    key = jax.random.wrap_key_data(
        jnp.copy(jax.random.key_data(d.pop("key"))))
    return RoundCarry(key=key, **{k: cp(v) for k, v in d.items()})


def _carry_to_tree(c: RoundCarry) -> dict:
    """The carry as a plain-dict pytree of ordinary arrays — NamedTuples
    unpacked and the PRNG key swapped for its uint32 key data, so the
    msgpack checkpointer round-trips every leaf bit-exactly."""
    d = c._asdict()
    d["bandit"] = d["bandit"]._asdict()
    d["key"] = jax.random.key_data(d["key"])
    return d


def _tree_to_carry(tree: dict) -> RoundCarry:
    """Inverse of :func:`_carry_to_tree` (re-wraps the PRNG key)."""
    d = dict(tree)
    d["bandit"] = gpcb.BanditState(**d["bandit"])
    d["key"] = jax.random.wrap_key_data(d["key"])
    return RoundCarry(**d)


def _sync_pool_stubs() -> dict:
    """Tiny placeholders for the buffered backend's pool fields — the
    sync backend has no in-flight pool, but ``RoundCarry`` is one shared
    NamedTuple, so the fields ride along as cheap constants (exactly like
    FedCor's ``fc_cov`` placeholder for the other selectors)."""
    return dict(pool_w=jnp.zeros((1,), jnp.float32),
                pool_d=jnp.zeros((1,), jnp.float32),
                pool_ids=jnp.zeros((1,), jnp.int32),
                pool_ready=jnp.zeros((1,), jnp.float32),
                pool_ver=jnp.zeros((1,), jnp.int32),
                clock=jnp.zeros((), jnp.float32),
                pool_ok=jnp.zeros((1,), bool),
                strikes=jnp.zeros((1,), jnp.int32),
                last_sel=jnp.zeros((1,), jnp.float32),
                sel_counts=jnp.zeros((1,), jnp.int32))


class ScanEngine:
    """Builds the dataset, trainer, evaluator, the jitted scan AND the
    deterministic pre-scan state (w^0, Algorithm 1 init phase, the
    per-selector host-RNG streams, the scenario streams) once; ``run()``
    only dispatches the scan, so repeated runs amortise both compile and
    initialization (the benchmark times a warm second run to separate
    compile from round throughput).

    Args:
        exp: the experiment config (selector, partition, rounds, ...).
        use_gp_kernel: force the Pallas GP kernel path (legacy knob;
            prefer ``gp_impl``).
        gp_impl: ``"auto"`` (kernel on TPU, jnp elsewhere), ``"kernel"``
            or ``"stacked"``.
        param_layout: ``"tree"`` (pytree carry, parity oracle) or
            ``"flat"`` (one contiguous ``(Dp,)`` workspace vector).
        use_ee: ``False`` → the Fig. 7 ablation (α = 0, no exploration).
        log_every: 0 silences in-scan progress prints.
        scenario: ``"full"`` / ``"availability"`` / ``"stragglers"`` or a
            ``repro.fl.latency.ScenarioConfig``.
        aggregation: ``"sync"`` (the paper's blocking rounds),
            ``"buffered"`` or a ``repro.fl.latency.AggregationConfig`` —
            the buffered backend scans aggregation EVENTS instead of
            rounds: K clients stay in flight at completion times drawn
            from the scenario's latency model, each event flushes the
            ``buffer_size`` earliest updates with staleness-discounted
            weights and dispatches their replacements (FedBuff).  The
            straggler deadline is meaningless here (nothing blocks), so
            a ``"stragglers"`` scenario contributes only its latency
            model.
        shard_clients: devices on the ``("clients",)`` mesh axis; > 1
            requires ``param_layout="flat"`` and K divisible by it.
        snapshot_every: > 0 segments the scan into chunks of N rounds and
            writes the carry (+ history so far) to ``snapshot_path``
            at every chunk boundary — resumable, bit-identical runs.
        snapshot_path: the snapshot file (required iff
            ``snapshot_every > 0``).
        faults: adversarial-client fault injection — ``None``, a mode
            name or a ``repro.fl.faults.FaultConfig``.  The per-round
            hit mask rides in as a precomputed scan input (independent
            host rng), and selected adversaries' updates are corrupted
            in-scan between local training and aggregation.
        aggregator: robust server aggregation — an aggregator name or a
            ``repro.fl.robust.RobustConfig``.  Anything but the plain
            ``"mean"`` default routes both scan bodies through the
            screened robust path: non-finite updates are masked out of
            aggregation AND out of GPFL's bandit feedback, and
            ``quarantine_after > 0`` masks repeat offenders out of
            in-scan selection through the availability plumbing.
        pre_selection: tiered pre-selection — ``None`` (off), ``"pooled"``
            or a ``repro.fl.preselect.PreselectConfig``.  Pooled runs a
            cheap tier-1 pass (``repro.core.gpcb.pool_scores``) inside
            every scan step, narrowing N clients to a ``pool_size`` pool
            the exact tier-2 selector is then restricted to; at
            ``pool_size >= N`` the pool is ``arange(N)`` and the run is
            bit-identical to the full-population engine.  With
            ``streamed=True`` the client tables stay host-resident and
            ``run()`` dispatches the double-buffered host-paced loop
            (``repro.fl.preselect.run_pooled_stream``) instead of the
            scan — peak device memory bounded by the pool, not N.
    """

    def __init__(self, exp: FLExperimentConfig, *,
                 use_gp_kernel: bool = False, gp_impl: str = "auto",
                 param_layout: str = "tree", use_ee: bool = True,
                 log_every: int = 0,
                 scenario: Union[str, ScenarioConfig, None] = "full",
                 aggregation: Union[str, AggregationConfig, None] = "sync",
                 shard_clients: int = 1, data=None,
                 defer_init: bool = False,
                 snapshot_every: int = 0,
                 snapshot_path: Optional[str] = None,
                 faults: Union[str, FaultConfig, None] = None,
                 aggregator: Union[str, RobustConfig, None] = "mean",
                 pre_selection: Union[str, PreselectConfig, None] = None,
                 telemetry: str = "off"):
        """Validate the combination against the capability registry, build
        data/trainer/streams (see the class docstring for every knob;
        ``data`` optionally injects a prebuilt ``(store, eval_x, eval_y)``
        so a Session can reuse one dataset across cells).  The scan jits
        lazily on the first ``run()`` — the batched multi-seed engine
        builds sub-engines purely for their state and never pays a
        per-seed compile.  ``defer_init=True`` (the batched engine's
        sub-engines only) skips the expensive Algorithm 1 init phase,
        leaving zero placeholders the batched engine overwrites with its
        seed-vmapped init — such an engine cannot ``run()`` itself."""
        self.aggregation = make_aggregation(aggregation)
        self.buffered = self.aggregation.kind == "buffered"
        # the robustness axis: fault injection + robust aggregation.
        # ``robust_active`` is THE gate for every robust-path branch in
        # the scan bodies — with it False the engine traces (and so runs)
        # bit-identically to an engine built before this layer existed.
        self.faults = make_faults(faults)
        self.robust = make_robust(aggregator)
        self.has_faults = self.faults.mode != "none"
        self.robust_active = (self.has_faults
                              or self.robust.aggregator != "mean"
                              or self.robust.quarantine_after > 0)
        # the pre-selection axis: ``pooled`` gates every tier-1 branch in
        # the scan bodies the same way ``robust_active`` gates the robust
        # path — with it False the engine traces bit-identically to an
        # engine built before this layer existed
        self.pre = make_preselect(pre_selection)
        self.pooled = self.pre.kind == "pooled"
        # the telemetry axis: ``counters`` gates every metric-emission
        # branch in the scan bodies exactly like ``robust_active`` /
        # ``pooled`` gate theirs — with it False the engine traces
        # bit-identically to an engine built before repro.obs existed
        self.telemetry = telemetry
        self.counters = telemetry in ("counters", "trace")
        self.tracing = telemetry == "trace"
        self.tracer = SpanTracer() if self.tracing else None
        validate_capabilities(SpecView(
            backend="scan", selector=exp.selector, param_layout=param_layout,
            scenario_kind=getattr(scenario, "kind", scenario or "full"),
            aggregation_kind=self.aggregation.kind,
            shard_clients=int(shard_clients), use_gp_kernel=use_gp_kernel,
            clients_per_round=exp.clients_per_round,
            snapshot_every=int(snapshot_every),
            fault_mode=self.faults.mode, aggregator=self.robust.aggregator,
            quarantine=int(self.robust.quarantine_after),
            preselect_kind=self.pre.kind,
            preselect_pool=int(self.pre.pool_size),
            preselect_streamed=bool(self.pre.streamed),
            telemetry=telemetry))
        # buffered: buffer size M (updates per aggregation event) and the
        # event count E — at M = K every event is a full sync round
        self.buffer_m = self.aggregation.resolved_buffer(
            exp.clients_per_round) if self.buffered else exp.clients_per_round
        self.events = self.aggregation.resolved_events(
            exp.rounds, exp.clients_per_round) if self.buffered \
            else exp.rounds
        self.snapshot_every = int(snapshot_every)
        self.snapshot_path = snapshot_path
        if self.snapshot_every > 0 and not snapshot_path:
            raise ValueError(
                f"snapshot_every={snapshot_every} needs a snapshot_path "
                f"to write the carry snapshots to")
        self.final_carry: Optional[RoundCarry] = None
        self.scenario = make_scenario(scenario)
        self.shard_clients = int(shard_clients)
        if self.shard_clients > 1:
            # K % shard_clients re-checked where the layout is derived
            self._cohort_rules = cohort_axis_rules(exp.clients_per_round,
                                                   self.shard_clients)
            if jax.device_count() < self.shard_clients:
                raise ValueError(
                    f"shard_clients={shard_clients} but only "
                    f"{jax.device_count()} jax device(s) are visible")
        self.exp = exp
        self.gp_impl = resolve_gp_impl(gp_impl, use_gp_kernel)
        self.param_layout = param_layout
        self.use_ee = use_ee
        self.log_every = log_every
        self.streamed = self.pooled and self.pre.streamed
        if self.streamed:
            # large-population mode: the tables stay HOST-resident and
            # ``run()`` dispatches ``run_pooled_stream`` — none of the
            # device-table machinery below is built (materialising the
            # full (N, cap) tables on device is exactly what streaming
            # avoids).  Keep an injected dataset only if its store is
            # actually host-resident.
            self._stream_data = data if (data is not None and getattr(
                data[0], "host_tables", False)) else None
            # how local training builds its minibatch rows (None until the
            # streamed loop builds its tables)
            self.minibatch_path = minibatch_path(
                self._stream_data[0].capacity) if self._stream_data else None
            self._defer_init = bool(defer_init)
            self._jit = {}
            return
        self.store, self.eval_x, self.eval_y = data if data is not None \
            else _build_data(exp, exp.seed)
        # how local training builds its minibatch rows: by the table height
        # and the backend alone, and the same rows either way, so it stays
        # out of ``fingerprint()``
        self.minibatch_path = minibatch_path(self.store.capacity)
        # the tier-1 pool size, clamped to the population (the registry
        # already guarantees pool_size >= K)
        self.pool_size = min(int(self.pre.pool_size),
                             self.store.n_clients) if self.pooled else 0
        if self.pooled and self.shard_clients > 1:
            # tier-1 scores elementwise over the population, so it shards
            # over the SAME ("clients",) mesh as the cohort step; fails
            # fast here when N does not divide evenly
            self._pop_rules = population_axis_rules(
                self.store.n_clients, self.shard_clients)
        self.trainer = make_cohort_trainer(exp)
        self.loss_eval = make_cohort_loss_eval(exp) \
            if exp.selector in ("powd", "fedcor") else None
        self.powd_d = exp.powd_d or powd_default_d(self.store.n_clients,
                                                   exp.clients_per_round)
        self.spec = None  # FlatSpec, set by _build_initial_state (flat only)
        self._mesh = None
        self._defer_init = defer_init
        self._kinit = None        # deferred init-phase key (gpfl only)
        self._params_tree = None  # pre-pack params for the deferred init
        if self.shard_clients > 1:
            from jax.sharding import Mesh
            self._mesh = Mesh(
                np.asarray(jax.devices()[: self.shard_clients]),
                ("clients",))
        self._inputs = self._build_initial_state()
        # lazily jitted dispatchers; a Session shares this dict across
        # config-modulo-seed sibling engines so one compile serves all
        self._jit: Dict[str, Any] = {"scan": None, "chunk": None}

    def _compiled(self):
        """The jitted full-run scan (all T rounds, or — buffered — the
        prefill prologue plus all E aggregation events), built on first
        use.  Donates the params/direction carries: XLA aliases them into
        the scan instead of holding a live caller copy (``run()`` passes
        copies)."""
        if self._jit["scan"] is None:
            build = self._build_event_scan if self.buffered \
                else self._build_scan
            self._jit["scan"] = jax.jit(build(), donate_argnums=(0, 1))
        return self._jit["scan"]

    def _compiled_prefill(self):
        """The jitted buffered prologue (select + train the initial K
        in-flight clients), used by the CHUNKED path only — the full-run
        dispatcher inlines the prefill into its single jit.  Not donated:
        its inputs are the engine's cached initial state."""
        if self._jit.get("prefill") is None:
            self._jit["prefill"] = jax.jit(self._build_prefill())
        return self._jit["prefill"]

    def _compiled_chunk(self):
        """The jitted N-round chunk scan (snapshot runs), built on first
        use.  Donates the WHOLE input carry — the caller either hands it
        fresh copies (round 0) or buffers it has already snapshotted to
        host (chunk boundaries), so donation never aliases live data."""
        if self._jit["chunk"] is None:
            self._jit["chunk"] = jax.jit(self._build_chunk(),
                                         donate_argnums=(0,))
        return self._jit["chunk"]

    # ---- the scan body: one complete federated round, fully on device ----
    def _build_body(self):
        """The per-round scan body, shared verbatim by the full-T scan
        and the N-round chunk scan — chunked execution therefore replays
        the unsegmented run's math bit-identically.

        Each stage of the round runs under a ``jax.named_scope``
        (``fl_select``, ``fl_gather``, ``fl_train``, ``fl_aggregate``,
        ``fl_evaluate``, ``fl_observe``): op metadata only, which lets a
        profiler trace put each device op under its stage."""
        exp, scn = self.exp, self.scenario
        N, K, T = self.store.n_clients, exp.clients_per_round, exp.rounds
        W = max(exp.fedcor_warmup, 2)   # FedCor needs 2 loss probes to rank
        # client tables + eval set ride in as RUNTIME arguments (not
        # closures) so the same traced scan can be vmapped over a seed
        # axis whose every element carries its own dataset
        trainer, loss_eval = self.trainer, self.loss_eval
        evaluate = make_table_evaluator(exp)
        use_ee, log_every = self.use_ee, self.log_every
        sel = exp.selector
        is_gpfl, is_random = sel == "gpfl", sel == "random"
        is_powd, is_fedcor = sel == "powd", sel == "fedcor"
        is_flat = self.param_layout == "flat"
        use_kernel = self.gp_impl == "kernel"
        has_avail = scn.kind == "availability"
        has_lat = scn.kind == "stragglers"
        deadline = scn.resolved_deadline() if has_lat else 0.0
        spec = self.spec
        shard = self.shard_clients
        faults, robust = self.faults, self.robust
        has_faults, robust_active = self.has_faults, self.robust_active
        quarantine = int(robust.quarantine_after)
        pooled, P = self.pooled, self.pool_size
        counters = self.counters

        if is_flat:
            if use_kernel:
                from repro.kernels.ops import gp_projection
                score_fn = gp_projection
            else:
                score_fn = gp_mod.gp_scores_matrix
        elif use_kernel:
            from repro.kernels.ops import gp_projection_tree
            score_fn = gp_projection_tree
        else:
            score_fn = gp_mod.gp_scores_stacked

        pool_scores_sharded = None
        if pooled and shard > 1:
            pop_P, pop_repl = cohort_specs(self._pop_rules)

            def _tier1(u, gp_term, last_sel, pj, t):
                # elementwise over this device's N/shard clients — the
                # only global reduction (the Eq. 5 softmax inside
                # ``gp_term``) is computed by the caller OUTSIDE the
                # mesh; the tiled all_gather restores the canonical
                # full-population row order for the top-k
                s_loc = gpcb.pool_scores(u, gp_term, last_sel, t, T, pj)
                return jax.lax.all_gather(s_loc, "clients", axis=0,
                                          tiled=True)

            pool_scores_sharded = jax.shard_map(
                _tier1, mesh=self._mesh,
                in_specs=(pop_P, pop_P, pop_P, pop_P, pop_repl),
                out_specs=pop_repl, check_vma=False)

        cohort_sharded = None
        if shard > 1:
            cohort_P, repl_P = cohort_specs(self._cohort_rules)

            def _cohort(params_vec, direction_vec, x, y, sizes, rng_raw):
                # per-device view: K/shard clients of this round's cohort
                rngs = jax.random.wrap_key_data(rng_raw)
                p_tree = flat_mod.unpack(spec, params_vec)
                w_i, d_i, _ = trainer(p_tree, x, y, sizes, rngs)
                w_loc = flat_mod.pack_stacked(spec, w_i)
                # tiled all-gather: row order == single-device pack, so the
                # gathered matrix (and everything downstream) is bit-equal
                w_mat = jax.lax.all_gather(w_loc, "clients", axis=0,
                                           tiled=True)
                if is_gpfl:
                    # each device projects ITS clients' momenta (Eq. 3)
                    # with the unsharded run's score function; rows are
                    # independent dots, so local == global values
                    with jax.named_scope("fl_observe"):
                        d_loc = flat_mod.pack_stacked(spec, d_i)
                        gp_loc = score_fn(d_loc, direction_vec)
                        gp = jax.lax.all_gather(gp_loc, "clients", axis=0,
                                                tiled=True)
                else:
                    gp = jnp.zeros((K,), jnp.float32)
                return w_mat, gp

            cohort_sharded = jax.shard_map(
                _cohort, mesh=self._mesh,
                in_specs=(repl_P, repl_P, cohort_P, cohort_P, cohort_P,
                          cohort_P),
                out_specs=(repl_P, repl_P), check_vma=False)

        def server_flat(w_mat, params_vec, direction_vec, weights):
            return server_update_flat(
                w_mat, params_vec, direction_vec, lr=exp.lr,
                gamma=exp.momentum, weights=weights, use_kernel=use_kernel)

        if shard > 1 and use_kernel:
            # XLA cannot partition a Mosaic kernel: every device runs the
            # server round on its replica of the gathered cohort, the
            # same program as the unsharded engine
            server_flat = jax.shard_map(server_flat, mesh=self._mesh,
                                        in_specs=repl_P, out_specs=repl_P,
                                        check_vma=False)

        def body(tabs, carry: RoundCarry, xs):
            x_tab, y_tab, sz_tab, eval_x, eval_y = tabs
            t, jitter, sel_ids, cand_ids, avail, lat, flt, pjit = xs
            with jax.named_scope("fl_select"):
                key, kt = jax.random.split(carry.key)
                avail_arg = avail if has_avail else None
                if quarantine > 0 and (is_gpfl or is_fedcor):
                    # quarantine repeat offenders out of in-scan selection
                    # via the avail plumbing — but never starve the cohort:
                    # if masking leaves fewer than K candidates, fall back
                    # to the unquarantined base set for this round
                    base = avail if has_avail else jnp.ones((N,), bool)
                    cand = base & (carry.strikes < quarantine)
                    enough = jnp.sum(cand.astype(jnp.int32)) >= K
                    avail_arg = jnp.where(enough, cand, base)
                params_in = flat_mod.unpack(spec, carry.params) if is_flat \
                    else carry.params

                # ---- tier-1 pre-selection: narrow N to the candidate pool
                pool_ids_r = pool_mask = sel_avail = None
                if pooled:
                    u = gpcb.gpcb_values(carry.bandit, T, exp.rho)
                    gp_term = gp_mod.normalize_gp(carry.latest_gp)
                    if pool_scores_sharded is not None:
                        # sharded runs never carry an avail mask (the robust
                        # and availability axes both reject shard_clients>1)
                        pscores = pool_scores_sharded(
                            u, gp_term, carry.last_sel, pjit, t)
                    else:
                        pscores = gpcb.pool_scores(
                            u, gp_term, carry.last_sel, t, T, pjit,
                            avail=avail_arg)
                    pool_ids_r = gpcb.pool_topk(pscores, P)
                    pool_mask = jnp.zeros((N,), bool).at[pool_ids_r].set(True)
                    base_m = avail_arg if avail_arg is not None \
                        else jnp.ones((N,), bool)
                    sel_avail = compose_selection_mask(pool_mask, base_m, K)

                # ---- selection (fixed-shape, pure jnp) ----
                all_losses = None
                if is_gpfl:
                    scores = gpcb.selection_scores(
                        carry.bandit, carry.latest_gp, jitter, t, T,
                        rho=exp.rho, use_ee=use_ee,
                        avail=sel_avail if pooled else avail_arg)
                    ids = jnp.argsort(-scores)[:K]
                elif is_random:
                    # pooled: the stream carries RANKS into the (sorted) pool
                    # — at P = N the pool is arange(N), so take(pool, ranks)
                    # replays random_id_stream's draws bit-identically
                    ids = jnp.take(pool_ids_r, sel_ids) if pooled else sel_ids
                elif is_powd:
                    cx, cy, csz = ClientStore.gather_tables(
                        x_tab, y_tab, sz_tab, cand_ids)
                    closs = loss_eval(params_in, cx, cy, csz)
                    if pooled:
                        # restrict the host-drawn candidates to the pool
                        # in-scan (the candidate stream itself must stay
                        # untouched for host-RNG parity); out-of-pool
                        # candidates rank -inf unless that would starve the
                        # top-K
                        in_pool = jnp.take(pool_mask, cand_ids)
                        enough_p = jnp.sum(in_pool.astype(jnp.int32)) >= K
                        closs = jnp.where(enough_p & ~in_pool, -jnp.inf,
                                          closs)
                    ids = jnp.take(cand_ids, jnp.argsort(-closs)[:K])
                else:  # fedcor
                    all_losses = loss_eval(params_in, x_tab, y_tab, sz_tab)
                    warm = (lambda: jnp.take(pool_ids_r, sel_ids)) if pooled \
                        else (lambda: sel_ids)
                    ids = jax.lax.cond(
                        t < W,
                        warm,
                        lambda: fedcor_greedy(
                            carry.fc_cov, K,
                            avail=sel_avail if pooled else avail_arg))
                ids = ids.astype(jnp.int32)

            # ---- cohort local training (vmapped; sharded when asked) ----
            with jax.named_scope("fl_gather"):
                x, y, sizes = ClientStore.gather_tables(x_tab, y_tab, sz_tab,
                                                        ids)
                rngs = jax.random.split(kt, K)
            with jax.named_scope("fl_train"):
                w_mat = w_i = d_i = gp_sharded = None
                if shard > 1:
                    w_mat, gp_sharded = cohort_sharded(
                        carry.params, carry.direction, x, y, sizes,
                        jax.random.key_data(rngs))
                else:
                    w_i, d_i, _ = trainer(params_in, x, y, sizes, rngs)

                # ---- adversarial corruption of the cohort's updates ----
                delivered = None
                if has_faults:
                    # corrupt the trainer's TREE output before any packing,
                    # so one corruption path serves both layouts (the robust
                    # constraint rejects shard_clients > 1, so w_i is live)
                    hit = jnp.take(flt, ids)
                    fkey = jax.random.fold_in(kt, 0x0F17)
                    w_i, d_i, delivered = corrupt_cohort(
                        faults, fkey, hit, w_i, d_i, params_in)

            # ---- straggler deadlines: late clients miss aggregation ----
            with jax.named_scope("fl_aggregate"):
                if has_lat:
                    done = jnp.take(lat, ids) <= deadline
                    cnt = jnp.sum(done.astype(jnp.float32))
                    # nobody made it → fall back to plain FedAvg over the
                    # cohort (the server cannot skip a round in fixed shapes)
                    weights = jnp.where(cnt > 0,
                                        done.astype(jnp.float32)
                                        / jnp.maximum(cnt, 1.0),
                                        jnp.full((K,), 1.0 / K, jnp.float32))
                else:
                    done, weights = None, None

                # ---- server update ----
                valid = None
                if robust_active:
                    # the non-finite screen: diverged/poisoned rows are
                    # masked out of aggregation entirely; dropped-out and
                    # straggler rows fold into the same validity mask (a
                    # masked mean over valid rows ≡ the legacy done-weighted
                    # FedAvg; an all-invalid round keeps params unchanged)
                    valid = finite_rows(w_i)
                    if delivered is not None:
                        valid = valid & delivered
                    if done is not None:
                        valid = valid & done
                    cohort = flat_mod.pack_stacked(spec, w_i) if is_flat \
                        else w_i
                    params = robust_aggregate(robust, cohort, carry.params,
                                              valid)
                    if is_flat:
                        direction = update_global_direction_flat(
                            carry.direction, carry.params, params, exp.lr,
                            exp.momentum)
                    else:
                        direction = update_global_direction(
                            carry.direction, carry.params, params, exp.lr,
                            exp.momentum)
                elif is_flat:
                    if w_mat is None:
                        # one (K, Dp) pack out of the trainer, then contiguous
                        # vector passes (or the fused Pallas server kernel)
                        w_mat = flat_mod.pack_stacked(spec, w_i)
                    params, direction = server_flat(
                        w_mat, carry.params, carry.direction, weights)
                else:
                    params = fedavg(w_i, weights)
                    direction = update_global_direction(
                        carry.direction, carry.params, params, exp.lr,
                        exp.momentum)

            # ---- evaluation of the new global model ----
            with jax.named_scope("fl_evaluate"):
                acc, gl_loss = evaluate(
                    flat_mod.unpack(spec, params) if is_flat else params,
                    eval_x, eval_y)

            # ---- per-selector feedback state ----
            with jax.named_scope("fl_observe"):
                if is_gpfl:
                    if gp_sharded is not None:
                        gp_scores = gp_sharded
                    else:
                        grads_in = flat_mod.pack_stacked(spec, d_i) \
                            if is_flat else d_i
                        gp_scores = score_fn(grads_in, carry.direction)
                    # robust path: corrupted rows must not write the bandit
                    # (their Eq. 3 scores are poisoned) — mask them out like
                    # straggler-dropped clients, plus any non-finite score
                    vm = valid & jnp.isfinite(gp_scores) if robust_active \
                        else done
                    bandit, latest_gp = gpcb.observe(
                        carry.bandit, carry.latest_gp, ids, gp_scores, acc,
                        gl_loss, valid_mask=vm)
                else:
                    bandit, latest_gp = carry.bandit, carry.latest_gp

                if is_fedcor:
                    fc_cov = jax.lax.cond(
                        t >= 1,
                        lambda: fedcor_cov_update(
                            carry.fc_cov, carry.fc_prev, all_losses,
                            beta=_FEDCOR_BETA),
                        lambda: carry.fc_cov)
                    fc_prev = all_losses
                else:
                    fc_cov, fc_prev = carry.fc_cov, carry.fc_prev

                seen = carry.seen.at[ids].set(True)
                cov = jnp.mean(seen.astype(jnp.float32))

                if log_every:
                    fmt = (f"[{exp.name}/scan] round {{r}}/{T} acc={{a:.4f}} "
                           "loss={l:.4f} cov={c:.2f}")
                    jax.lax.cond(
                        (t + 1) % log_every == 0,
                        lambda op: jax.debug.print(fmt, r=op[0] + 1, a=op[1],
                                                   l=op[2], c=op[3]),
                        lambda op: None,
                        (t, acc, gl_loss, cov))

                out = {"ids": ids, "acc": acc, "loss": gl_loss,
                       "coverage": cov}
                rep = dict(params=params, direction=direction, bandit=bandit,
                           latest_gp=latest_gp, seen=seen, key=key,
                           fc_cov=fc_cov, fc_prev=fc_prev)
                if quarantine > 0:
                    # a strike = a DETECTABLY corrupt update that arrived
                    # (dropout rows never arrive, so they cannot offend)
                    offense = ~finite_rows(w_i)
                    if delivered is not None:
                        offense = offense & delivered
                    rep["strikes"] = carry.strikes.at[ids].add(
                        offense.astype(jnp.int32))
                if pooled:
                    rep["last_sel"] = carry.last_sel.at[ids].set(
                        jnp.asarray(t, jnp.float32))
                    out["pool"] = pool_ids_r
                if counters:
                    # the telemetry axis: per-round metric counters as extra
                    # scan outs — everything here reuses values the body
                    # already materialised, and NONE of it is traced when the
                    # gate is off (the off-mode bit-parity contract)
                    rep["sel_counts"] = carry.sel_counts.at[ids].add(1)
                    if robust_active:
                        n_del = jnp.sum(valid.astype(jnp.float32))
                    elif has_lat:
                        n_del = jnp.sum(done.astype(jnp.float32))
                    else:
                        n_del = jnp.asarray(float(K), jnp.float32)
                    if is_gpfl and d_i is not None:
                        align = obs_metrics.alignment_cosine(
                            gp_scores, obs_metrics.cohort_sq_norms(d_i))
                    else:
                        align = jnp.zeros((), jnp.float32)
                    out.update({
                        "m_participants": jnp.asarray(float(K), jnp.float32),
                        "m_delivered": n_del,
                        "m_selection_entropy":
                            obs_metrics.selection_entropy(rep["sel_counts"]),
                        "m_gp_alignment": align,
                        "m_screened": (K - n_del) if robust_active
                            else jnp.zeros((), jnp.float32),
                        "m_quarantined": jnp.sum(
                            (rep["strikes"] >= quarantine)
                            .astype(jnp.float32)) if quarantine > 0
                            else jnp.zeros((), jnp.float32),
                        "m_pool_recall": jnp.mean(
                            jnp.take(pool_mask, ids).astype(jnp.float32))
                            if pooled else jnp.ones((), jnp.float32),
                    })
            return carry._replace(**rep), out

        return body

    def _build_scan(self):
        """The full-T dispatcher: builds round-0 carry, scans all rounds."""
        body = self._build_body()
        N, T = self.store.n_clients, self.exp.rounds
        quarantine = int(self.robust.quarantine_after)
        pooled = self.pooled
        counters = self.counters

        def run_scan(params, direction, bandit, latest_gp, fc_cov, fc_prev,
                     key, streams, tables, eval_tabs):
            jitter, sel_ids, cand_ids, avail, lat, flt, pjit = streams
            tabs = tables + eval_tabs
            pool = _sync_pool_stubs()
            if quarantine > 0:
                pool["strikes"] = jnp.zeros((N,), jnp.int32)
            if pooled:
                pool["last_sel"] = jnp.full((N,), -1.0, jnp.float32)
            if counters:
                pool["sel_counts"] = jnp.zeros((N,), jnp.int32)
            carry0 = RoundCarry(params, direction, bandit, latest_gp,
                                jnp.zeros((N,), bool), key, fc_cov, fc_prev,
                                **pool)
            return jax.lax.scan(
                functools.partial(body, tabs), carry0,
                (jnp.arange(T), jitter, sel_ids, cand_ids, avail, lat, flt,
                 pjit))

        return run_scan

    # -------------------- the buffered (FedBuff) event-scan backend ----
    def _build_prefill(self):
        """The buffered prologue: sync round 0's selection + training,
        except the K trained updates go into the in-flight pool instead
        of being aggregated — event 0 flushes the earliest of them.  Key
        splits and stream rows are consumed exactly as the sync body's
        round 0 does, which is what makes the M = K zero-latency parity
        bit-exact."""
        exp, scn = self.exp, self.scenario
        N, K, E = self.store.n_clients, exp.clients_per_round, self.events
        trainer, loss_eval = self.trainer, self.loss_eval
        sel = exp.selector
        is_gpfl, is_random = sel == "gpfl", sel == "random"
        is_powd = sel == "powd"
        is_flat = self.param_layout == "flat"
        has_avail = scn.kind == "availability"
        use_ee = self.use_ee
        spec = self.spec
        faults, has_faults = self.faults, self.has_faults
        quarantine = int(self.robust.quarantine_after)
        pooled, P = self.pooled, self.pool_size
        counters = self.counters

        def prefill(params, direction, bandit, latest_gp, fc_cov, fc_prev,
                    key, streams, tables):
            jitter, sel_ids, cand_ids, avail, lat, flt, pjit = streams
            x_tab, y_tab, sz_tab = tables
            with jax.named_scope("fl_select"):
                key, kt = jax.random.split(key)
                avail_arg = avail[0] if has_avail else None
                params_in = flat_mod.unpack(spec, params) if is_flat \
                    else params

                # tier-1 pool at dispatch slot 0 (pool jitter row 0 — the
                # event body consumes row t = e + 1, the stream discipline)
                last_sel = jnp.full((N,), -1.0, jnp.float32) if pooled \
                    else jnp.zeros((1,), jnp.float32)
                pool_ids_r = pool_mask = sel_avail = None
                if pooled:
                    u = gpcb.gpcb_values(bandit, E, exp.rho)
                    gp_term = gp_mod.normalize_gp(latest_gp)
                    pscores = gpcb.pool_scores(u, gp_term, last_sel, 0, E,
                                               pjit[0], avail=avail_arg)
                    pool_ids_r = gpcb.pool_topk(pscores, P)
                    pool_mask = jnp.zeros((N,), bool).at[pool_ids_r].set(True)
                    base_m = avail_arg if avail_arg is not None \
                        else jnp.ones((N,), bool)
                    sel_avail = compose_selection_mask(pool_mask, base_m, K)

                if is_gpfl:
                    scores = gpcb.selection_scores(
                        bandit, latest_gp, jitter[0], 0, E,
                        rho=exp.rho, use_ee=use_ee,
                        avail=sel_avail if pooled else avail_arg)
                    ids = jnp.argsort(-scores)[:K]
                elif is_random:
                    ids = jnp.take(pool_ids_r, sel_ids[0]) if pooled \
                        else sel_ids[0]
                elif is_powd:
                    cx, cy, csz = ClientStore.gather_tables(
                        x_tab, y_tab, sz_tab, cand_ids[0])
                    closs = loss_eval(params_in, cx, cy, csz)
                    if pooled:
                        in_pool = jnp.take(pool_mask, cand_ids[0])
                        enough_p = jnp.sum(in_pool.astype(jnp.int32)) >= K
                        closs = jnp.where(enough_p & ~in_pool, -jnp.inf,
                                          closs)
                    ids = jnp.take(cand_ids[0], jnp.argsort(-closs)[:K])
                else:  # fedcor: round 0 is always warm-up (W >= 2), but the
                    # all-client probe still runs and seeds fc_prev
                    fc_prev = loss_eval(params_in, x_tab, y_tab, sz_tab)
                    ids = jnp.take(pool_ids_r, sel_ids[0]) if pooled \
                        else sel_ids[0]
                ids = ids.astype(jnp.int32)
                if pooled:
                    last_sel = last_sel.at[ids].set(0.0)

            with jax.named_scope("fl_gather"):
                x, y, sizes = ClientStore.gather_tables(x_tab, y_tab, sz_tab,
                                                        ids)
                rngs = jax.random.split(kt, K)
            with jax.named_scope("fl_train"):
                w_i, d_i, _ = trainer(params_in, x, y, sizes, rngs)
                pool_ok = jnp.ones((K,), bool)
                if has_faults:
                    # stream row 0 belongs to the prefill (event e consumes
                    # row e+1) — same row discipline as the selector streams
                    hit = jnp.take(flt[0], ids)
                    fkey = jax.random.fold_in(kt, 0x0F17)
                    w_i, d_i, pool_ok = corrupt_cohort(
                        faults, fkey, hit, w_i, d_i, params_in)
            strikes = jnp.zeros((N,) if quarantine > 0 else (1,),
                                jnp.int32)
            # the prefill is dispatch slot 0: its cohort seeds the
            # selection tally the event body's entropy counter reads
            sel_counts = jnp.zeros((N,), jnp.int32).at[ids].add(1) \
                if counters else jnp.zeros((1,), jnp.int32)
            return RoundCarry(
                params=params, direction=direction, bandit=bandit,
                latest_gp=latest_gp, seen=jnp.zeros((N,), bool), key=key,
                fc_cov=fc_cov, fc_prev=fc_prev,
                pool_w=flat_mod.pack_stacked(spec, w_i) if is_flat else w_i,
                pool_d=flat_mod.pack_stacked(spec, d_i) if is_flat else d_i,
                pool_ids=ids, pool_ready=jnp.take(lat[0], ids),
                pool_ver=jnp.zeros((K,), jnp.int32),
                clock=jnp.zeros((), jnp.float32),
                pool_ok=pool_ok, strikes=strikes, last_sel=last_sel,
                sel_counts=sel_counts)

        return prefill

    def _build_event_body(self):
        """One buffered aggregation event, fully on device: flush the M
        earliest-completing in-flight updates with staleness-discounted
        FedAvg weights, evaluate, feed the FRESH updates to GPFL's
        bandit, then select + train the M replacement clients against
        the just-aggregated model.  Event e dispatches cohort slot
        t = e + 1, consuming stream row t and one key split — the sync
        body's round-t discipline.  The stages carry the sync body's
        ``fl_*`` scopes."""
        exp, scn = self.exp, self.scenario
        N, K = self.store.n_clients, exp.clients_per_round
        M, E = self.buffer_m, self.events
        W = max(exp.fedcor_warmup, 2)
        discount = float(self.aggregation.staleness_discount)
        trainer, loss_eval = self.trainer, self.loss_eval
        evaluate = make_table_evaluator(exp)
        use_ee, log_every = self.use_ee, self.log_every
        sel = exp.selector
        is_gpfl, is_random = sel == "gpfl", sel == "random"
        is_powd, is_fedcor = sel == "powd", sel == "fedcor"
        is_flat = self.param_layout == "flat"
        use_kernel = self.gp_impl == "kernel"
        has_avail = scn.kind == "availability"
        spec = self.spec
        faults, robust = self.faults, self.robust
        has_faults, robust_active = self.has_faults, self.robust_active
        quarantine = int(robust.quarantine_after)
        pooled, P = self.pooled, self.pool_size
        counters = self.counters

        if is_flat:
            if use_kernel:
                from repro.kernels.ops import gp_projection
                score_fn = gp_projection
            else:
                score_fn = gp_mod.gp_scores_matrix
        elif use_kernel:
            from repro.kernels.ops import gp_projection_tree
            score_fn = gp_projection_tree
        else:
            score_fn = gp_mod.gp_scores_stacked

        def take(tree, idx):
            return jax.tree.map(lambda a: jnp.take(a, idx, axis=0), tree)

        def body(tabs, carry: RoundCarry, xs):
            x_tab, y_tab, sz_tab, eval_x, eval_y = tabs
            e, jitter, sel_row, cand_row, avail, lat, flt, pjit = xs
            t = e + 1   # the dispatch slot: sync round t's stream row
            avail_arg = avail if has_avail else None

            # ---- flush the M earliest-completing in-flight updates ----
            with jax.named_scope("fl_aggregate"):
                # stable argsort: equal ready times keep pool (= dispatch)
                # order, which the zero-latency parity contract relies on
                order = jnp.argsort(carry.pool_ready, stable=True)
                flush, keep = order[:M], order[M:]
                f_ids = jnp.take(carry.pool_ids, flush)
                # dispatch at event j stamps version j+1, so a slot flushed
                # at the very next event has staleness 0
                staleness = e - jnp.take(carry.pool_ver, flush)
                lam = jnp.power(discount, staleness.astype(jnp.float32))
                all_fresh = jnp.all(staleness == 0)
                w_flush = take(carry.pool_w, flush)
                d_flush = take(carry.pool_d, flush)
                # the server "wakes up" when the M-th update lands; kept
                # slots complete later and new dispatches start from here,
                # so the clock is monotone
                clock = jnp.take(carry.pool_ready, order[M - 1])

                valid = None
                if robust_active:
                    # the flush's validity mask: undelivered (dropout) slots
                    # plus non-finite rows are screened out of aggregation
                    valid = jnp.take(carry.pool_ok, flush) \
                        & finite_rows(w_flush)
                    params = robust_aggregate(robust, w_flush, carry.params,
                                              valid, weights=lam)
                    if is_flat:
                        direction = update_global_direction_flat(
                            carry.direction, carry.params, params, exp.lr,
                            exp.momentum)
                    else:
                        direction = update_global_direction(
                            carry.direction, carry.params, params, exp.lr,
                            exp.momentum)
                # an all-fresh buffer takes the sync engine's weights=None
                # reduction (jnp.mean is NOT bitwise a uniform tensordot),
                # so discount=1.0 + zero latency is bit-identical to sync
                elif is_flat:
                    params, direction = jax.lax.cond(
                        all_fresh,
                        lambda: server_update_flat(
                            w_flush, carry.params, carry.direction, lr=exp.lr,
                            gamma=exp.momentum, weights=None,
                            use_kernel=use_kernel),
                        lambda: server_update_flat(
                            w_flush, carry.params, carry.direction, lr=exp.lr,
                            gamma=exp.momentum, weights=lam / jnp.sum(lam),
                            use_kernel=use_kernel))
                else:
                    params = jax.lax.cond(
                        all_fresh,
                        lambda: fedavg(w_flush, None),
                        lambda: fedavg(w_flush, lam / jnp.sum(lam)))
                    direction = update_global_direction(
                        carry.direction, carry.params, params, exp.lr,
                        exp.momentum)

            # ---- evaluation of the new global model ----
            with jax.named_scope("fl_evaluate"):
                acc, gl_loss = evaluate(
                    flat_mod.unpack(spec, params) if is_flat else params,
                    eval_x, eval_y)

            # ---- feedback: only FRESH updates may touch the bandit ----
            # (their momenta are projections against a direction the
            # server has since moved past — Eq. 3 scores of stale
            # updates are meaningless, so they are masked out exactly
            # like straggler-dropped clients in the sync backend)
            with jax.named_scope("fl_observe"):
                if is_gpfl:
                    gp_scores = score_fn(d_flush, carry.direction)
                    vm = staleness == 0
                    if robust_active:
                        # corrupted flushes must not write the bandit either
                        vm = vm & valid & jnp.isfinite(gp_scores)
                    bandit, latest_gp = gpcb.observe(
                        carry.bandit, carry.latest_gp, f_ids, gp_scores, acc,
                        gl_loss, valid_mask=vm)
                else:
                    bandit, latest_gp = carry.bandit, carry.latest_gp

                seen = carry.seen.at[f_ids].set(True)
                cov = jnp.mean(seen.astype(jnp.float32))

                # strike accounting happens at FLUSH time (when corruption
                # becomes observable), before this event's dispatch selection
                strikes = carry.strikes
                if quarantine > 0:
                    offense = jnp.take(carry.pool_ok, flush) \
                        & ~finite_rows(w_flush)
                    strikes = strikes.at[f_ids].add(offense.astype(jnp.int32))

            # ---- dispatch M replacements against the new model ----
            with jax.named_scope("fl_select"):
                key, kt = jax.random.split(carry.key)
                params_in = flat_mod.unpack(spec, params) if is_flat \
                    else params
                fc_cov, fc_prev = carry.fc_cov, carry.fc_prev
                if quarantine > 0 and (is_gpfl or is_fedcor):
                    # same starvation guard as the sync body, at need = M
                    base = avail if has_avail else jnp.ones((N,), bool)
                    cand = base & (strikes < quarantine)
                    enough = jnp.sum(cand.astype(jnp.int32)) >= M
                    avail_arg = jnp.where(enough, cand, base)
                # tier-1 pool for THIS dispatch, scored against the
                # just-updated bandit/GP state (like the tier-2 dispatch)
                pool_ids_r = pool_mask = sel_avail = None
                if pooled:
                    u = gpcb.gpcb_values(bandit, E, exp.rho)
                    gp_term = gp_mod.normalize_gp(latest_gp)
                    pscores = gpcb.pool_scores(u, gp_term, carry.last_sel, t,
                                               E, pjit, avail=avail_arg)
                    pool_ids_r = gpcb.pool_topk(pscores, P)
                    pool_mask = jnp.zeros((N,), bool).at[pool_ids_r].set(True)
                    base_m = avail_arg if avail_arg is not None \
                        else jnp.ones((N,), bool)
                    sel_avail = compose_selection_mask(pool_mask, base_m, M)
                if is_gpfl:
                    scores = gpcb.selection_scores(
                        bandit, latest_gp, jitter, t, E, rho=exp.rho,
                        use_ee=use_ee,
                        avail=sel_avail if pooled else avail_arg)
                    n_ids = jnp.argsort(-scores)[:M]
                elif is_random:
                    n_ids = jnp.take(pool_ids_r, sel_row[:M]) if pooled \
                        else sel_row[:M]
                elif is_powd:
                    cx, cy, csz = ClientStore.gather_tables(
                        x_tab, y_tab, sz_tab, cand_row)
                    closs = loss_eval(params_in, cx, cy, csz)
                    if pooled:
                        in_pool = jnp.take(pool_mask, cand_row)
                        enough_p = jnp.sum(in_pool.astype(jnp.int32)) >= M
                        closs = jnp.where(enough_p & ~in_pool, -jnp.inf,
                                          closs)
                    n_ids = jnp.take(cand_row, jnp.argsort(-closs)[:M])
                else:  # fedcor: probe the NEW model, select with the
                    # PRE-update covariance, then fold the probe in — the
                    # sync body's round-t ordering (t = e+1 >= 1, so the
                    # EMA update is unconditional here)
                    all_losses = loss_eval(params_in, x_tab, y_tab, sz_tab)
                    warm = (lambda: jnp.take(pool_ids_r, sel_row[:M])) \
                        if pooled else (lambda: sel_row[:M])
                    n_ids = jax.lax.cond(
                        t < W,
                        warm,
                        lambda: fedcor_greedy(
                            carry.fc_cov, M,
                            avail=sel_avail if pooled else avail_arg))
                    fc_cov = fedcor_cov_update(carry.fc_cov, carry.fc_prev,
                                               all_losses, beta=_FEDCOR_BETA)
                    fc_prev = all_losses
                n_ids = n_ids.astype(jnp.int32)

            with jax.named_scope("fl_gather"):
                x, y, sizes = ClientStore.gather_tables(x_tab, y_tab, sz_tab,
                                                        n_ids)
                rngs = jax.random.split(kt, M)
            with jax.named_scope("fl_train"):
                w_i, d_i, _ = trainer(params_in, x, y, sizes, rngs)
                new_ok = jnp.ones((M,), bool)
                if has_faults:
                    # this xs row is stream row t = e + 1 (the event scan
                    # slices row 0 off for the prefill), i.e. the dispatch
                    # slot's row — the sync body's round-t discipline
                    hit = jnp.take(flt, n_ids)
                    fkey = jax.random.fold_in(kt, 0x0F17)
                    w_i, d_i, new_ok = corrupt_cohort(
                        faults, fkey, hit, w_i, d_i, params_in)
                new_w = flat_mod.pack_stacked(spec, w_i) if is_flat else w_i
                new_d = flat_mod.pack_stacked(spec, d_i) if is_flat else d_i

                def cat(kept, new):
                    return jax.tree.map(
                        lambda a, b: jnp.concatenate([a, b], axis=0), kept,
                        new)

                pool_w = cat(take(carry.pool_w, keep), new_w)
                pool_d = cat(take(carry.pool_d, keep), new_d)
                pool_ids = jnp.concatenate([jnp.take(carry.pool_ids, keep),
                                            n_ids])
                pool_ready = jnp.concatenate(
                    [jnp.take(carry.pool_ready, keep),
                     clock + jnp.take(lat, n_ids)])
                pool_ver = jnp.concatenate(
                    [jnp.take(carry.pool_ver, keep),
                     jnp.full((M,), t, jnp.int32)])

            with jax.named_scope("fl_observe"):
                if log_every:
                    fmt = (f"[{exp.name}/scan] event {{r}}/{E} acc={{a:.4f}} "
                           "loss={l:.4f} cov={c:.2f}")
                    jax.lax.cond(
                        (e + 1) % log_every == 0,
                        lambda op: jax.debug.print(fmt, r=op[0] + 1, a=op[1],
                                                   l=op[2], c=op[3]),
                        lambda op: None,
                        (e, acc, gl_loss, cov))

                out = {"ids": f_ids, "acc": acc, "loss": gl_loss,
                       "coverage": cov, "sim_time": clock}
                rep = dict(params=params, direction=direction, bandit=bandit,
                           latest_gp=latest_gp, seen=seen, key=key,
                           fc_cov=fc_cov, fc_prev=fc_prev, pool_w=pool_w,
                           pool_d=pool_d, pool_ids=pool_ids,
                           pool_ready=pool_ready, pool_ver=pool_ver,
                           clock=clock)
                if robust_active:
                    rep["pool_ok"] = jnp.concatenate(
                        [jnp.take(carry.pool_ok, keep), new_ok])
                if quarantine > 0:
                    rep["strikes"] = strikes
                if pooled:
                    rep["last_sel"] = carry.last_sel.at[n_ids].set(
                        jnp.asarray(t, jnp.float32))
                    out["pool"] = pool_ids_r
                if counters:
                    # per-event metric counters (extra scan outs; never
                    # traced with the gate off — the off-mode parity
                    # contract).  The selection tally counts DISPATCHES
                    # (n_ids), matching the sync body's per-round cohort.
                    rep["sel_counts"] = carry.sel_counts.at[n_ids].add(1)
                    if robust_active:
                        n_del = jnp.sum(valid.astype(jnp.float32))
                    else:
                        n_del = jnp.asarray(float(M), jnp.float32)
                    if is_gpfl:
                        align = obs_metrics.alignment_cosine(
                            gp_scores, obs_metrics.cohort_sq_norms(d_flush))
                    else:
                        align = jnp.zeros((), jnp.float32)
                    out.update({
                        "m_participants": jnp.asarray(float(M), jnp.float32),
                        "m_delivered": n_del,
                        "m_selection_entropy":
                            obs_metrics.selection_entropy(rep["sel_counts"]),
                        "m_gp_alignment": align,
                        "m_screened": (M - n_del) if robust_active
                            else jnp.zeros((), jnp.float32),
                        "m_quarantined": jnp.sum(
                            (strikes >= quarantine)
                            .astype(jnp.float32)) if quarantine > 0
                            else jnp.zeros((), jnp.float32),
                        "m_pool_recall": jnp.mean(
                            jnp.take(pool_mask, n_ids).astype(jnp.float32))
                            if pooled else jnp.ones((), jnp.float32),
                        "m_staleness_hist":
                            obs_metrics.staleness_histogram(staleness),
                    })
            return carry._replace(**rep), out

        return body

    def _build_event_scan(self):
        """The buffered full-run dispatcher: prefill the pool (sync
        round 0's cohort) and scan all E aggregation events, one jit.
        Event e consumes stream row e+1 — row 0 belongs to the
        prefill — so at E = T the selector streams' first T rows are
        consumed exactly as the sync scan consumes them."""
        prefill = self._build_prefill()
        body = self._build_event_body()
        E = self.events

        def run_scan(params, direction, bandit, latest_gp, fc_cov, fc_prev,
                     key, streams, tables, eval_tabs):
            tabs = tables + eval_tabs
            carry0 = prefill(params, direction, bandit, latest_gp, fc_cov,
                             fc_prev, key, streams, tables)
            jitter, sel_ids, cand_ids, avail, lat, flt, pjit = \
                (s[1:] for s in streams)
            return jax.lax.scan(
                functools.partial(body, tabs), carry0,
                (jnp.arange(E), jitter, sel_ids, cand_ids, avail, lat,
                 flt, pjit))

        return run_scan

    def _build_chunk(self):
        """The chunk dispatcher: scans an N-round (buffered: N-event)
        segment from an explicit carry (round/event offsets ride in as
        the ``ts`` input; the buffered caller pre-shifts the stream
        slices by one row for the prefill)."""
        body = self._build_event_body() if self.buffered \
            else self._build_body()

        def run_chunk(carry, ts, streams, tables, eval_tabs):
            jitter, sel_ids, cand_ids, avail, lat, flt, pjit = streams
            tabs = tables + eval_tabs
            return jax.lax.scan(
                functools.partial(body, tabs), carry,
                (ts, jitter, sel_ids, cand_ids, avail, lat, flt, pjit))

        return run_chunk

    def _build_initial_state(self):
        """The pre-scan state: params at w^0, Algorithm 1's init phase,
        the per-selector host-RNG streams and the scenario streams.
        Deterministic in ``exp.seed`` (scenario streams in the scenario's
        own seed), so it is computed once here and reused by every
        ``run()``.  In the flat layout this is also where the static
        ``FlatSpec`` is derived and the initial params/direction are
        packed.

        Host-parity invariant: ``rng_np`` is consumed in EXACTLY the
        order the host loop's selector consumes it (stream functions in
        ``repro.core.selector`` document each selector's draws); the
        scenario streams draw from an independent generator so enabling a
        scenario never shifts the selector streams.
        """
        exp, scn = self.exp, self.scenario
        N, K, T = self.store.n_clients, exp.clients_per_round, exp.rounds
        # buffered runs need one stream row per dispatch: the prefill
        # (row 0) plus one per event — every stream function consumes its
        # rng strictly row-by-row, so at E = T the first T rows are
        # bit-identical to the sync streams (the parity contract)
        R = self.events + 1 if self.buffered else T
        rng_np = np.random.default_rng(exp.seed)
        key = jax.random.key(exp.seed)
        key, k0 = jax.random.split(key)
        params = small.init(k0, exp.model)

        # -- scenario streams (independent host rng; scan-only semantics) --
        avail_np = lat_np = None
        if scn.kind == "availability":
            need = max(K, self.powd_d) if exp.selector == "powd" else K
            srng = np.random.default_rng((exp.seed, scn.seed, 1))
            avail_np = availability_stream(srng, R, N, scn.availability,
                                           need)
        if scn.kind == "stragglers" or self.buffered:
            # buffered aggregation ALWAYS draws completion times — they
            # are its event clock, whatever the scenario kind
            srng = np.random.default_rng((exp.seed, scn.seed, 2))
            lat_np = completion_time_stream(
                dataclasses.replace(scn.latency, n_clients=N), srng, R)
        flt_np = None
        if self.has_faults:
            # fault stream: tag 3 of the tuple-seeded scenario rng family
            # (availability is 1, latency 2) — enabling faults never
            # shifts the selector or scenario streams
            frng = np.random.default_rng((exp.seed, self.faults.seed, 3))
            flt_np = fault_stream(frng, R, N, self.faults)

        # -- selector streams: replay the host loop's rng consumption --
        jitter = np.zeros((R, 1), np.float32)
        sel_ids = np.zeros((R, 1), np.int32)
        cand_ids = np.zeros((R, 1), np.int32)
        if exp.selector == "gpfl":
            # Algorithm 1 init phase — shared with the host loop so the
            # seed GPs (and hence round-0 selection) are bit-identical.
            key, kinit = jax.random.split(key)
            if self._defer_init:
                # the batched engine overwrites these placeholders with
                # its seed-vmapped init phase (same key, same chunks)
                self._kinit, self._params_tree = kinit, params
                direction = tree_zeros_like(params)
                latest_gp = jnp.zeros((N,), jnp.float32)
            else:
                direction, gp_all = init_gp_phase(self.trainer, self.store,
                                                  params, kinit)
                latest_gp = jnp.asarray(gp_all, jnp.float32)
            jitter = np.asarray(gpfl_jitter_stream(rng_np, R, N), np.float32)
        else:
            direction = tree_zeros_like(params)
            latest_gp = jnp.zeros((N,), jnp.float32)
            if exp.selector == "random":
                # pooled: the stream carries ranks INTO the sorted tier-1
                # pool (at pool_size = N it consumes the rng exactly as
                # random_id_stream does — the bit-parity contract; the
                # pooled × availability combination is registry-rejected,
                # so avail_np is always None here when pooled)
                sel_ids = (pool_rank_stream(rng_np, R, self.pool_size, K)
                           if self.pooled else
                           random_id_stream(rng_np, R, N, K,
                                            avail=avail_np)).astype(np.int32)
            elif exp.selector == "powd":
                cand_ids = powd_candidate_stream(
                    rng_np, R, N, self.powd_d,
                    avail=avail_np).astype(np.int32)
            elif exp.selector == "fedcor":
                sel_ids = (pool_rank_stream(rng_np, R, self.pool_size, K,
                                            upto=max(exp.fedcor_warmup, 2))
                           if self.pooled else
                           fedcor_warmup_stream(
                               rng_np, R, N, K, exp.fedcor_warmup,
                               avail=avail_np)).astype(np.int32)
        bandit = gpcb.init_state(N)

        if exp.selector == "fedcor":
            fc_cov = jnp.eye(N, dtype=jnp.float32)
            fc_prev = jnp.zeros((N,), jnp.float32)
        else:
            fc_cov = jnp.zeros((1, 1), jnp.float32)
            fc_prev = jnp.zeros((1,), jnp.float32)

        if self.param_layout == "flat":
            self.spec = flat_mod.make_flat_spec(params)
            params = flat_mod.pack(self.spec, params)
            direction = flat_mod.pack(self.spec, direction)

        pjit_np = None
        if self.pooled:
            # the dedicated pool tie-break stream: tag 4 of the
            # tuple-seeded side-stream family (availability 1, latency 2,
            # faults 3) — enabling pre-selection never shifts the legacy
            # selector or scenario streams
            prng = np.random.default_rng((exp.seed, self.pre.seed, 4))
            pjit_np = pool_jitter_stream(prng, R, N).astype(np.float32)

        streams = (
            jnp.asarray(jitter),
            jnp.asarray(sel_ids),
            jnp.asarray(cand_ids),
            jnp.asarray(avail_np) if avail_np is not None
            else jnp.zeros((R, 1), bool),
            jnp.asarray(lat_np) if lat_np is not None
            else jnp.zeros((R, 1), jnp.float32),
            jnp.asarray(flt_np) if flt_np is not None
            else jnp.zeros((R, 1), bool),
            jnp.asarray(pjit_np) if pjit_np is not None
            else jnp.zeros((R, 1), jnp.float32),
        )
        return (params, direction, bandit, latest_gp, fc_cov, fc_prev, key,
                streams)

    # ------------------------------------------------ snapshot machinery
    def fingerprint(self) -> str:
        """Identity of this engine's math: the experiment config plus
        every knob that changes per-round numerics.  Stamped into each
        snapshot's meta; a resume against a different fingerprint fails
        fast instead of silently mixing runs.  (``snapshot_every`` is
        deliberately EXCLUDED — chunk boundaries don't change the math,
        so a resume may use a different cadence.)"""
        payload = {
            "exp": dataclasses.asdict(self.exp),
            "param_layout": self.param_layout,
            "scenario": (self.scenario.kind, self.scenario.seed,
                         self.scenario.availability,
                         self.scenario.deadline_s),
            "use_ee": self.use_ee,
            "gp_impl": self.gp_impl,
            "aggregation": (self.aggregation.kind, int(self.buffer_m),
                            int(self.events),
                            float(self.aggregation.staleness_discount)),
            "faults": (self.faults.mode, float(self.faults.fraction),
                       float(self.faults.noise_sigma),
                       float(self.faults.signflip_scale),
                       float(self.faults.prob), int(self.faults.seed)),
            "robust": (self.robust.aggregator,
                       float(self.robust.trim_fraction),
                       float(self.robust.clip_quantile),
                       int(self.robust.quarantine_after)),
            "pre_selection": (self.pre.kind, int(self.pre.pool_size),
                              int(self.pre.seed), bool(self.pre.streamed)),
            # telemetry never changes the math, but ``counters`` changes
            # the carry/out STRUCTURE (sel_counts + m_* buffers), so an
            # off-mode snapshot must not restore into a counters engine
            # (or vice versa); "counters" and "trace" share structure
            "counters": self.counters,
        }
        return hashlib.sha1(
            json.dumps(payload, sort_keys=True).encode()).hexdigest()

    def _fresh_carry(self) -> RoundCarry:
        """Round-0 carry assembled from the cached initial state (shared
        references — callers must copy before donating).  Buffered: the
        pool fields are STRUCTURAL zeros — the real initial pool comes
        from the prefill dispatch; this carry only serves as the restore
        template (and the sync chunk path's round-0 state)."""
        (params, direction, bandit, latest_gp, fc_cov, fc_prev, key,
         _streams) = self._inputs
        if self.buffered:
            K = self.exp.clients_per_round

            def z(t):
                return jax.tree.map(
                    lambda a: jnp.zeros((K,) + a.shape, a.dtype), t)

            pool = dict(pool_w=z(params), pool_d=z(params),
                        pool_ids=jnp.zeros((K,), jnp.int32),
                        pool_ready=jnp.zeros((K,), jnp.float32),
                        pool_ver=jnp.zeros((K,), jnp.int32),
                        clock=jnp.zeros((), jnp.float32),
                        pool_ok=jnp.ones((K,), bool),
                        strikes=jnp.zeros((1,), jnp.int32),
                        last_sel=jnp.zeros((1,), jnp.float32),
                        sel_counts=jnp.zeros((1,), jnp.int32))
        else:
            pool = _sync_pool_stubs()
        if self.robust.quarantine_after > 0:
            pool["strikes"] = jnp.zeros((self.store.n_clients,), jnp.int32)
        if self.pooled:
            pool["last_sel"] = jnp.full((self.store.n_clients,), -1.0,
                                        jnp.float32)
        if self.counters:
            pool["sel_counts"] = jnp.zeros((self.store.n_clients,),
                                           jnp.int32)
        return RoundCarry(params, direction, bandit, latest_gp,
                          jnp.zeros((self.store.n_clients,), bool), key,
                          fc_cov, fc_prev, **pool)

    def _empty_outs(self) -> Dict[str, np.ndarray]:
        """Preallocated full-run host buffers for the scan outputs
        (chunks fill rows [t, t+n); fixed shapes keep the snapshot
        restorable without knowing how far the run got).  Sync: T rounds
        of K selections; buffered: E events of M flushes, plus the
        simulated-clock trace."""
        R, C = self.events, self.buffer_m
        outs = {"ids": np.zeros((R, C), np.int32),
                "acc": np.zeros((R,), np.float32),
                "loss": np.zeros((R,), np.float32),
                "coverage": np.zeros((R,), np.float32)}
        if self.buffered:
            outs["sim_time"] = np.zeros((R,), np.float32)
        if self.pooled:
            outs["pool"] = np.zeros((R, self.pool_size), np.int32)
        if self.counters:
            for k in obs_metrics.metric_out_keys(self.buffered):
                if k.endswith(obs_metrics.STALENESS_HIST_KEY):
                    outs[k] = np.zeros((R, obs_metrics.STALENESS_BINS),
                                       np.float32)
                else:
                    outs[k] = np.zeros((R,), np.float32)
        return outs

    def _write_snapshot(self, carry: RoundCarry, outs: dict,
                        rounds_done: int) -> None:
        """Persist carry + history at a chunk boundary (atomic rename).
        ``save_checkpoint`` device_gets every leaf, i.e. the bytes are
        host copies taken BEFORE the carry is donated onward."""
        save_checkpoint(
            self.snapshot_path, {"carry": _carry_to_tree(carry),
                                 "out": outs},
            step=int(rounds_done),
            meta={"fingerprint": self.fingerprint(),
                  "rounds": int(rounds_done),
                  "total_rounds": int(self.events),
                  "snapshot_every": int(self.snapshot_every)})

    def _read_snapshot(self):
        """Restore ``(carry, outs, rounds_done)`` from ``snapshot_path``.

        Raises:
            ValueError: the snapshot was written by a different
                experiment/engine configuration (fingerprint mismatch).
        """
        # fingerprint first (cheap meta peek): a different run's snapshot
        # may not even share this engine's carry STRUCTURE (e.g. pooled
        # pre-selection adds carry/output leaves), so the identity check
        # must precede the structural restore
        want = self.fingerprint()
        _, meta = peek_meta(self.snapshot_path)
        got = (meta or {}).get("fingerprint")
        if got != want:
            raise ValueError(
                f"snapshot {self.snapshot_path} belongs to a different "
                f"run (fingerprint {got!r} != this engine's {want!r}); "
                f"refusing to resume from it")
        like = {"carry": _carry_to_tree(self._fresh_carry()),
                "out": self._empty_outs()}
        tree, step, meta = restore_checkpoint(self.snapshot_path, like,
                                              return_meta=True)
        # np.array (not asarray): restored leaves can be read-only
        # frombuffer views, and the chunk loop writes rows in place
        outs = {k: np.array(v) for k, v in tree["out"].items()}
        return _tree_to_carry(tree["carry"]), outs, int(step)

    # --------------------------------------------------------- dispatch
    def run(self, *, resume: bool = False,
            until_round: Optional[int] = None) -> Optional[RunResult]:
        """Dispatch the compiled scan → the full T-round history.

        Without snapshots (``snapshot_every == 0``) this is ONE device
        dispatch covering all T rounds.  With ``snapshot_every = n`` the
        run executes as ceil(T/n) chunked dispatches, persisting the
        carry after each one — bit-identical history, restart-safe.

        Args:
            resume: restore ``snapshot_path`` if it exists and continue
                from its round (a fresh run when no snapshot exists, so
                restart scripts stay idempotent).  Requires
                ``snapshot_every > 0``.
            until_round: stop (and snapshot) after this many rounds
                instead of finishing — a budgeted slice of the run that
                a later ``resume=True`` call completes.  Requires
                ``snapshot_every > 0``.

        Returns:
            ``repro.fl.simulation.RunResult`` with the accuracy/loss
            curves, the (T, K) selection log, per-client selection
            counts, coverage and the amortised per-round wall time —
            or ``None`` when ``until_round`` stopped the run early (the
            state lives in the snapshot file).
        """
        if self._defer_init:
            raise RuntimeError(
                "this ScanEngine was built with defer_init=True (a "
                "BatchedSeedEngine sub-engine); its init-phase state may "
                "be a placeholder — run the batched engine instead")
        if self.streamed:
            # large-population mode: host-paced double-buffered loop, no
            # scan (the registry already rejects snapshots here)
            if resume or until_round is not None:
                raise ValueError(
                    "streamed pre-selection does not snapshot; "
                    "resume/until_round are unavailable")
            return run_pooled_stream(self.exp, self.pre,
                                     data=self._stream_data,
                                     log_every=self.log_every,
                                     telemetry=self.telemetry,
                                     tracer=self.tracer)
        if self.snapshot_every <= 0:
            if resume or until_round is not None:
                raise ValueError(
                    "resume/until_round require snapshot_every > 0 (and "
                    "a snapshot_path): there is no snapshot state "
                    "without a snapshot cadence")
            return self._run_single()
        return self._run_chunked(resume=resume, until_round=until_round)

    def _span(self, name: str, **args):
        """A program span (``repro.obs.trace``): the profiler annotation
        in every telemetry mode, plus the Chrome event under
        ``telemetry="trace"``."""
        return (self.tracer or _NULL_TRACER).span(name, **args)

    def _scan_args(self) -> tuple:
        """The full-run scan's arguments.  params/direction are donated
        to the scan — they are fresh copies so the cached initial state
        survives for the next run()."""
        (params, direction, bandit, latest_gp, fc_cov, fc_prev, key,
         streams) = self._inputs
        return (jax.tree.map(jnp.copy, params),
                jax.tree.map(jnp.copy, direction),
                bandit, latest_gp, fc_cov, fc_prev, key, streams,
                self.store.tables(), (self.eval_x, self.eval_y))

    def lower(self):
        """The snapshot-free full-run scan lowered for this engine's
        inputs, for inspecting the compiled program (``.compile()``,
        ``.as_text()``) without running it."""
        return self._compiled().lower(*self._scan_args())

    def _run_single(self) -> RunResult:
        """The snapshot-free fast path: one dispatch for the whole run
        (all T rounds, or — buffered — prefill + all E events)."""
        t0 = time.perf_counter()
        with self._span("copy_inputs"):
            args = self._scan_args()
        with self._span("scan_dispatch", rounds=int(self.events)):
            carry, out = jax.block_until_ready(self._compiled()(*args))
        scan_wall = time.perf_counter() - t0
        self.final_carry = carry
        with self._span("fetch_outputs"):
            outs = {k: np.asarray(v) for k, v in out.items()}
        with self._span("build_result"):
            return self._result(outs, wall=scan_wall,
                                rounds_timed=self.events)

    def _run_chunked(self, *, resume: bool,
                     until_round: Optional[int]) -> Optional[RunResult]:
        """Segmented execution: chunks of ``snapshot_every`` rounds
        (buffered: events), the carry snapshotted (host-copied first)
        after every chunk."""
        E = self.events
        stop = E if until_round is None else min(int(until_round), E)
        if until_round is not None and until_round < 1:
            raise ValueError(f"until_round must be >= 1; got {until_round}")
        streams = self._inputs[7]
        t = 0
        outs = self._empty_outs()
        tables, eval_tabs = self.store.tables(), (self.eval_x, self.eval_y)
        if resume and os.path.exists(self.snapshot_path):
            carry, outs, t = self._read_snapshot()
        elif self.buffered:
            # event 0's carry comes from the prefill dispatch; COPY it —
            # a jit may alias pass-through outputs (params, bandit, ...)
            # to its inputs, i.e. to the engine's cached initial state,
            # which the chunk's whole-carry donation must never consume
            (params, direction, bandit, latest_gp, fc_cov, fc_prev, key,
             _s) = self._inputs
            with self._span("prefill_dispatch"):
                carry = _copy_carry(self._compiled_prefill()(
                    params, direction, bandit, latest_gp, fc_cov, fc_prev,
                    key, streams, tables))
        else:
            # round 0: fresh copies, so the cached initial state survives
            # the chunk's whole-carry donation
            carry = _copy_carry(self._fresh_carry())

        t0 = time.perf_counter()
        ran = 0
        # buffered chunks shift the stream window by one row: row 0 was
        # the prefill's, event e consumes row e+1
        ofs = 1 if self.buffered else 0
        while t < stop:
            n = min(self.snapshot_every, stop - t)
            ts = jnp.arange(t, t + n)
            chunk_streams = tuple(s[t + ofs:t + n + ofs] for s in streams)
            with self._span("chunk_dispatch", start=int(t), rounds=int(n)):
                carry, out = jax.block_until_ready(self._compiled_chunk()(
                    carry, ts, chunk_streams, tables, eval_tabs))
            for name, v in out.items():
                outs[name][t:t + n] = np.asarray(v)
            t += n
            ran += n
            # device_get inside the save copies the carry to host BEFORE
            # the next chunk donates (and invalidates) its buffers
            with self._span("snapshot_write", rounds_done=int(t)):
                self._write_snapshot(carry, outs, t)
        wall = time.perf_counter() - t0
        self.final_carry = carry

        if stop < E:
            return None  # budgeted slice done; state lives in the snapshot
        return self._result(outs, wall=wall, rounds_timed=max(ran, 1))

    def _result(self, outs: dict, *, wall: float,
                rounds_timed: int) -> RunResult:
        """Assemble the RunResult from full-run host output buffers
        (T sync rounds or E buffered events)."""
        exp = self.exp
        N, R = self.store.n_clients, self.events
        selections = np.asarray(outs["ids"])
        counts = np.bincount(selections.reshape(-1),
                             minlength=N).astype(np.int64)
        sim = outs.get("sim_time")
        pool = outs.get("pool")
        metrics = None
        if self.counters:
            # in-scan counts → host-side exact byte accounting (int64,
            # derived from the flat workspace's padded size Dp — the
            # wire slab both layouts logically move)
            dp = padded_param_count(small.count_params(exp.model))
            metrics = obs_metrics.finalize_metrics(
                obs_metrics.MetricBuffer.from_scan_outs(outs),
                param_bytes=dp * BYTES_PER_PARAM)
        return RunResult(
            config=exp,
            accuracy=np.asarray(outs["acc"], np.float32),
            loss=np.asarray(outs["loss"], np.float32),
            selections=selections,
            # one (or few) dispatches cover the whole run — report the
            # amortised per-round wall time of the rounds THIS call ran
            # (the first call includes the scan's compile)
            round_time_s=np.full((R,), wall / max(rounds_timed, 1),
                                 np.float32),
            selection_counts=counts,
            coverage=np.asarray(outs["coverage"], np.float32),
            sim_time_s=None if sim is None
            else np.asarray(sim, np.float32),
            pools=None if pool is None
            else np.asarray(pool, np.int32),
            metrics=metrics,
        )


def _stack_trees(trees):
    """Stack a list of identically-shaped pytrees along a new axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class BatchedSeedEngine:
    """S seeds of ONE experiment config in ONE vmapped scan dispatch.

    A multi-seed sweep is embarrassingly batchable: the cells share every
    static property (shapes, selector, rounds) and differ only in data
    content, initial params and host-RNG streams.  This engine builds one
    :class:`ScanEngine` per seed purely for its *state* (dataset, init
    phase, streams — the per-seed jit never compiles), stacks all of it
    along a leading seed axis, and runs ``jax.vmap`` of the single-seed
    round-scan as one jitted dispatch: S seeds cost one trace/compile and
    one device round-trip instead of S.

    Client tables are zero-padded to the tallest per-seed ``ClientStore``
    capacity before stacking; this is invisible to the math (batch
    sampling never indexes past a client's true size, and the loss probe
    reduces over a fixed ``batch_cap`` height — see
    ``repro.fl.client.make_cohort_loss_eval``), so every seed's selection
    history stays bit-identical to its sequential ``ScanEngine`` run
    (pinned by ``tests/test_api.py`` for all four selectors).

    Args:
        cells: experiment configs that differ only in ``seed`` (and
            ``name``) — what ``Plan.seeds(...)`` expands to.
        data_provider: optional ``cell -> (store, eval_x, eval_y)``
            callable (a Session's dataset cache); ``None`` builds each
            seed's dataset directly.
        use_gp_kernel / gp_impl / param_layout / use_ee / scenario: as on
            :class:`ScanEngine`.
        aggregation: accepted for signature parity with ``ScanEngine``
            (a Session forwards ``ExecutionSpec.engine_kwargs()``) but
            must resolve to ``"sync"`` — the buffered event-scan is not
            seed-batchable; a Session runs buffered cells sequentially.
        shard_clients: accepted for signature parity with ``ScanEngine``
            but must be 1 — the vmapped seed axis and the shard_map
            cohort mesh would nest.
        faults / aggregator: accepted for signature parity with
            ``ScanEngine`` but must resolve inert (``mode="none"`` /
            plain ``"mean"``, no quarantine) — robustness cells run
            sequentially (a Session routes them that way).
        pre_selection: accepted for signature parity with ``ScanEngine``
            but must resolve to ``kind="none"`` — the tier-1 pool pass
            carries per-cell state (``last_sel``), so pooled cells run
            sequentially (a Session routes them that way too).
        telemetry: ``"off"`` or ``"counters"`` — counter outs vmap like
            any other scan out, so counters cells still batch.
            ``"trace"`` is rejected: vmapped seeds share ONE dispatch,
            so per-seed spans would be meaningless (a Session runs trace
            cells sequentially).

    Raises:
        ValueError: cells disagree on anything but seed/name, or the
            registry rejects the combination.
    """

    def __init__(self, cells: Sequence[FLExperimentConfig], *,
                 data_provider: Optional[Callable] = None,
                 use_gp_kernel: bool = False, gp_impl: str = "auto",
                 param_layout: str = "tree", use_ee: bool = True,
                 scenario: Union[str, ScenarioConfig, None] = "full",
                 aggregation: Union[str, AggregationConfig, None] = "sync",
                 shard_clients: int = 1,
                 faults: Union[str, FaultConfig, None] = None,
                 aggregator: Union[str, RobustConfig, None] = "mean",
                 pre_selection: Union[str, PreselectConfig, None] = None,
                 telemetry: str = "off"):
        """Build per-seed state, stack it, and jit the vmapped scan."""
        if not cells:
            raise ValueError("BatchedSeedEngine needs at least one cell")
        if telemetry == "trace":
            raise ValueError(
                "telemetry='trace' cannot combine with the batched seed "
                "axis (vmapped seeds share one dispatch, so per-seed "
                "spans are meaningless); run trace cells sequentially "
                "(a Session does this automatically)")
        flt, rb = make_faults(faults), make_robust(aggregator)
        if (flt.mode != "none" or rb.aggregator != "mean"
                or rb.quarantine_after > 0):
            raise ValueError(
                "fault injection / robust aggregation cannot combine with "
                "the batched seed axis; run robustness cells sequentially "
                "(a Session does this automatically)")
        if make_preselect(pre_selection).kind != "none":
            raise ValueError(
                "pre_selection cannot combine with the batched seed axis; "
                "run pooled cells sequentially (a Session does this "
                "automatically)")
        if int(shard_clients) != 1:
            raise ValueError(
                f"shard_clients={shard_clients} cannot combine with the "
                f"batched seed axis (the vmapped seeds and the shard_map "
                f"cohort mesh would nest); run sharded cells sequentially")
        agg = make_aggregation(aggregation)
        if agg.kind != "sync":
            raise ValueError(
                f"aggregation={agg.kind!r} cannot combine with the "
                f"batched seed axis; run buffered cells sequentially "
                f"(a Session does this automatically)")
        base = cells[0]
        validate_capabilities(SpecView(
            backend="scan", selector=base.selector,
            param_layout=param_layout,
            scenario_kind=getattr(scenario, "kind", scenario or "full"),
            aggregation_kind=agg.kind,
            shard_clients=int(shard_clients), use_gp_kernel=use_gp_kernel,
            clients_per_round=base.clients_per_round,
            batch_seeds=len(cells), telemetry=telemetry))
        self.telemetry = telemetry
        self.counters = telemetry == "counters"
        key0 = dataclasses.replace(base, seed=0, name="")
        for c in cells[1:]:
            if dataclasses.replace(c, seed=0, name="") != key0:
                raise ValueError(
                    "BatchedSeedEngine cells must share one config modulo "
                    f"seed/name; {c.name!r} differs from {base.name!r}")
        self.cells = list(cells)
        self.engines = [
            ScanEngine(c, use_gp_kernel=use_gp_kernel, gp_impl=gp_impl,
                       param_layout=param_layout, use_ee=use_ee,
                       scenario=scenario,
                       data=data_provider(c) if data_provider else None,
                       defer_init=True, telemetry=telemetry)
            for c in cells]
        self._batched_inputs = self._stack_inputs()
        if base.selector == "gpfl":
            self._batched_inputs = self._batched_init_phase(
                self._batched_inputs)
        self._scan = jax.jit(jax.vmap(self.engines[0]._build_scan()))

    def _stack_inputs(self):
        """Stack every seed's pre-scan state (and tables) along axis 0."""
        per = [e._inputs for e in self.engines]
        stacked = []
        for j in range(len(per[0])):
            parts = [p[j] for p in per]
            if j == 6:  # PRNG keys: stack the raw key data, re-wrap
                raw = jnp.stack([jax.random.key_data(k) for k in parts])
                stacked.append(jax.random.wrap_key_data(raw))
            else:
                stacked.append(_stack_trees(parts))
        # client tables: zero-pad to the tallest per-seed capacity (the
        # loss probe's fixed-height reduction keeps this bit-invisible)
        cap = max(e.store.capacity for e in self.engines)
        xs, ys, szs = [], [], []
        for e in self.engines:
            x, y, sz = e.store.tables()
            pad = cap - x.shape[1]
            if pad:
                x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                y = jnp.pad(y, ((0, 0), (0, pad)))
            xs.append(x)
            ys.append(y)
            szs.append(sz)
        tables = (jnp.stack(xs), jnp.stack(ys), jnp.stack(szs))
        eval_tabs = (jnp.stack([e.eval_x for e in self.engines]),
                     jnp.stack([e.eval_y for e in self.engines]))
        return tuple(stacked) + (tables, eval_tabs)

    def _batched_init_phase(self, inputs):
        """Algorithm 1's init phase for ALL seeds at once (gpfl only).

        Sequential engines each pay their own trainer trace/compile to
        run the every-client init training; here the same chunked loop
        runs ONE ``vmap`` over the seed axis per chunk — identical keys
        (``fold_in(kinit, chunk_offset)``), identical chunking, identical
        math, so each seed's seed-GP vector (and hence its round-0
        selection) stays bit-identical to ``init_gp_phase``.

        Returns the stacked inputs with the direction / latest_gp
        placeholders replaced.
        """
        e0 = self.engines[0]
        N = e0.store.n_clients
        trainer = e0.trainer
        params_b = _stack_trees([e._params_tree for e in self.engines])
        kinits = jax.random.wrap_key_data(jnp.stack(
            [jax.random.key_data(e._kinit) for e in self.engines]))
        x_b, y_b, sz_b = inputs[8]   # stacked, common-capacity tables
        chunk = INIT_CHUNK           # shared with init_gp_phase (parity)

        def one_seed(params, kinit, x, y, sz, ofs):
            rngs = jax.random.split(jax.random.fold_in(kinit, ofs),
                                    x.shape[0])
            _, d_i, _ = trainer(params, x, y, sz, rngs)
            return d_i

        # ofs rides in as an argument so every full-size chunk shares ONE
        # compile (the tail chunk is the only second compilation)
        chunk_fn = jax.jit(jax.vmap(one_seed,
                                    in_axes=(0, 0, 0, 0, 0, None)))
        momenta = []
        for ofs in range(0, N, chunk):
            sl = slice(ofs, min(ofs + chunk, N))
            momenta.append(chunk_fn(params_b, kinits, x_b[:, sl],
                                    y_b[:, sl], sz_b[:, sl], ofs))
        momenta = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                               *momenta)
        direction = jax.tree.map(lambda m: jnp.mean(m, axis=1), momenta)
        gp_all = jax.vmap(gp_mod.gp_scores_stacked)(momenta, direction)
        if e0.param_layout == "flat":
            direction = jax.vmap(lambda t: flat_mod.pack(e0.spec, t))(
                direction)
        out = list(inputs)
        out[1] = direction
        out[3] = gp_all.astype(jnp.float32)
        return tuple(out)

    def run(self) -> List[RunResult]:
        """Dispatch the vmapped scan once → one history per seed.

        Returns:
            One ``RunResult`` per cell, in cell order.  Each result's
            ``round_time_s`` reports the amortised per-(seed, round)
            share of the single dispatch's wall time (the first call
            includes the compile).
        """
        (params, direction, bandit, latest_gp, fc_cov, fc_prev, keys,
         streams, tables, eval_tabs) = self._batched_inputs
        t0 = time.perf_counter()
        _, out = jax.block_until_ready(self._scan(
            params, direction, bandit, latest_gp, fc_cov, fc_prev, keys,
            streams, tables, eval_tabs))
        wall = time.perf_counter() - t0

        S = len(self.cells)
        results = []
        for s, cell in enumerate(self.cells):
            T = cell.rounds
            N = self.engines[s].store.n_clients
            selections = np.asarray(out["ids"][s])
            counts = np.bincount(selections.reshape(-1),
                                 minlength=N).astype(np.int64)
            metrics = None
            if self.counters:
                # counter outs carry the seed axis like every other out —
                # slice seed s's rows and finalise exactly as the
                # sequential engine does
                dp = padded_param_count(small.count_params(cell.model))
                metrics = obs_metrics.finalize_metrics(
                    obs_metrics.MetricBuffer.from_scan_outs(
                        {k: v[s] for k, v in out.items()}),
                    param_bytes=dp * BYTES_PER_PARAM)
            results.append(RunResult(
                config=cell,
                accuracy=np.asarray(out["acc"][s], np.float32),
                loss=np.asarray(out["loss"][s], np.float32),
                selections=selections,
                round_time_s=np.full((T,), wall / max(S * T, 1),
                                     np.float32),
                selection_counts=counts,
                coverage=np.asarray(out["coverage"][s], np.float32),
                metrics=metrics,
            ))
        return results


def run_batched_seeds(exp: FLExperimentConfig, seeds: Sequence[int],
                      **knobs) -> List[RunResult]:
    """One-shot convenience over :class:`BatchedSeedEngine`.

    Args:
        exp: the base experiment config.
        seeds: seeds to batch into one vmapped dispatch.
        **knobs: forwarded to :class:`BatchedSeedEngine`.

    Returns:
        One ``RunResult`` per seed, in ``seeds`` order.
    """
    cells = [dataclasses.replace(exp, seed=int(s), name=f"{exp.name}/seed={s}")
             for s in seeds]
    return BatchedSeedEngine(cells, **knobs).run()


def run_experiment_scan(exp: FLExperimentConfig, *, log_every: int = 0,
                        use_gp_kernel: bool = False, gp_impl: str = "auto",
                        param_layout: str = "tree",
                        use_ee: bool = True,
                        scenario: Union[str, ScenarioConfig, None] = "full",
                        aggregation: Union[str, AggregationConfig,
                                           None] = "sync",
                        shard_clients: int = 1,
                        faults: Union[str, FaultConfig, None] = None,
                        aggregator: Union[str, RobustConfig,
                                          None] = "mean",
                        pre_selection: Union[str, PreselectConfig,
                                             None] = None,
                        telemetry: str = "off") -> RunResult:
    """One-shot convenience over ``ScanEngine`` — the ``backend="scan"``
    entry point of ``repro.fl.run_experiment`` (see that function and the
    ``ScanEngine`` docstring for every knob)."""
    return ScanEngine(exp, use_gp_kernel=use_gp_kernel, gp_impl=gp_impl,
                      param_layout=param_layout, use_ee=use_ee,
                      log_every=log_every, scenario=scenario,
                      aggregation=aggregation,
                      shard_clients=shard_clients, faults=faults,
                      aggregator=aggregator,
                      pre_selection=pre_selection,
                      telemetry=telemetry).run()
