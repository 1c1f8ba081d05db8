"""One reader per per-layer metric, ``<metric name>.py``, loaded by path.

``read(trace, counts)`` takes the reduced trace (``bench.trace_reduce``)
and the run's counts (the driver's, plus ``peak``, ``window_s``,
``busy_s``) and returns the value, or ``None`` where the trace holds
nothing to read."""
