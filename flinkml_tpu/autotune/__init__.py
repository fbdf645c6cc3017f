"""Measurement-driven autotuning — guessed defaults become measured ones.

The heuristics that pick the executor's cache identities are guesses:
``infer_plan``'s ascending-communication-cost preset order, the serving
engine's power-of-two dispatch bucket cap and batching window, and the
three sort-class layout gates (GBT ``FLINKML_TPU_GBT_HISTOGRAM``, ALS
``FLINKML_TPU_ALS_REDUCTION``, W2V ``FLINKML_TPU_W2V_ACCUM``) that have
been "flip on a measured win" since they landed. This package measures
them
(:mod:`flinkml_tpu.autotune.search`) and pins winners into a committed,
mesh-keyed tuning table (:mod:`flinkml_tpu.autotune.table`) consulted at
key-construction time: an explicit env var or argument always wins, the
table supplies the default, and the static fallback only fires when the
current mesh has no measured entry.

Run the search::

    python -m flinkml_tpu.autotune --quick          # measure + print
    python -m flinkml_tpu.autotune --commit          # rewrite the table
    python -m flinkml_tpu.autotune --check           # CI schema gate

``FLINKML_TPU_AUTOTUNE=0`` disables every table consult (pure static
defaults — the escape hatch). See
``docs/development/compile_cache.md`` for the table format and runbook.
"""

from flinkml_tpu.autotune.table import (  # noqa: F401
    DEFAULT_TABLE_PATH,
    KNOWN_KNOBS,
    TuningTable,
    load_table,
    mesh_key,
    tuned_default,
)

__all__ = [
    "DEFAULT_TABLE_PATH",
    "KNOWN_KNOBS",
    "TuningTable",
    "load_table",
    "mesh_key",
    "tuned_default",
]
