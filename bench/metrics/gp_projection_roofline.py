"""The GP projection kernel's share of its roofline: the least time the
chip could take for its calls (``bench.counts.gp_kernel``: (K+1)·Dp·4
bytes read, 2·(K+1)·Dp FLOPs; the larger of bytes over HBM bandwidth and
FLOPs over peak) over the device time of its events in the trace.

The kernel's events are the device ops named ``gp_projection.<n>``: on a
v5e the Mosaic call carries the name of the jitted wrapper
(``repro.kernels.ops.gp_projection``), while the Pallas kernel function
itself is ``_kernel`` in both of the repository's kernels.
"""
from bench.counts.gp_kernel import least_seconds

KERNEL_OPS = ("gp_projection",)


def read(trace, counts):
    names = [n for n in trace["op_seconds"]
             if any(n == k or n.startswith(k + ".") for k in KERNEL_OPS)]
    secs = sum(trace["op_seconds"][n] for n in names)
    calls = sum(trace["op_counts"][n] for n in names)
    if not names or secs <= 0 or not counts.get("gp_calls"):
        return None
    least, _bound = least_seconds(counts["gp_k"], counts["gp_d"],
                                  counts["peak"])
    return 100.0 * least * calls / secs
