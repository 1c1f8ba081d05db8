"""`repro.obs` — observability: in-scan counters, cost accounting, tracing.

The subsystem sits behind the ``telemetry="off"|"counters"|"trace"`` axis of
the capability registry (see ``repro.api.capabilities``):

* ``"off"`` — the engine traces **bit-identically** to an engine built
  before this subsystem existed (Python-level gate, same pattern as
  ``robust_active`` / ``pooled``).
* ``"counters"`` — deterministic per-round / per-event metric counters are
  emitted as extra `lax.scan` outs from both the sync round body and the
  buffered event body (:mod:`repro.obs.metrics`), then finalised host-side
  with exact byte accounting (:mod:`repro.obs.cost`).
* ``"trace"`` — counters **plus** a host-side span tracer that emits
  Chrome/Perfetto trace-event JSON around input copies, jit dispatches,
  output fetches, ``device_put`` slabs and snapshot writes
  (:mod:`repro.obs.trace`).

In every mode those spans are ``jax.profiler.TraceAnnotation``s, so a
``jax.profiler`` capture places them on the device trace's clock; and the
traced programs carry ``jax.named_scope`` stage names (``fl_select`` …
``fl_observe`` in the round engine, ``gpfl_score``/``gpfl_gate``/
``step_grad``/``step_update`` in the gated step, ``ssd_intra_chunk``/
``ssd_inter_chunk`` in the SSD scan) in their ops' metadata, which the
device trace reports per op.  Both are metadata: the numbers are the same.

Per-cell metric rows are persisted to a JSONL sink keyed by
``cell_fingerprint`` and joined back against the run journal
(:mod:`repro.obs.export`).
"""
from repro.obs.cost import (
    CostModel,
    bytes_curve,
    bytes_per_round,
    cost_model,
    flops_per_local_step,
)
from repro.obs.export import MetricSink, join_journal, merge_sinks
from repro.obs.metrics import (
    METRIC_KEYS,
    METRIC_PREFIX,
    STALENESS_BINS,
    MetricBuffer,
    finalize_metrics,
)
from repro.obs.trace import (SPAN_NAMES, NullTracer, SpanTracer,
                             validate_trace)

__all__ = [
    "CostModel",
    "METRIC_KEYS",
    "METRIC_PREFIX",
    "MetricBuffer",
    "MetricSink",
    "NullTracer",
    "SPAN_NAMES",
    "STALENESS_BINS",
    "SpanTracer",
    "bytes_curve",
    "bytes_per_round",
    "cost_model",
    "finalize_metrics",
    "flops_per_local_step",
    "join_journal",
    "merge_sinks",
    "validate_trace",
]
