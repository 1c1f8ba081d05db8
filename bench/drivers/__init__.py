"""One module per kind of entry point, found by the cell's ``driver``.

A module defines ``Driver(config, workload, seed)`` with ``unit`` (what
the end-to-end rate counts), ``setup()``, ``step(spans) -> units``,
``drain(spans)``, ``counts(units, steps) -> dict`` for the metric
readers, ``release()``, ``check() -> {name: {"value", "limit"}}``,
``failed`` and ``check_overhead_s`` (set-up seconds spent only on
recording what the check compares, left out of ``setup_s``)."""
