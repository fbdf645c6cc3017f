"""Per-layer metric readers, found by the ``reader`` key of a metric's
file. Each module has ``read(params, obs) -> float | None``; ``obs`` is
what a traced run observed::

    {"trace": trace.reduce(...) or None,
     "counters": {"<group>.<counter>": delta over the window, ...},
     "setup_counters": {... delta over set-up ...},
     "units": {"steps": ..., "calls": ..., "rows": ..., ...},
     "traced_units": the same, for the traced slice only,
     "cell": the cell's file, "config": the configuration's file,
     "peaks": this device's row of peaks.json}

A reader that finds nothing to read returns None and the harness leaves
the metric out of the line."""
