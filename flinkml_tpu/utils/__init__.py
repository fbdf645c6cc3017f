"""Operational utilities: metrics, tracing/profiling.

SURVEY.md §5: the reference has no bespoke observability subsystem — it
re-registers Flink ``InternalOperatorMetricGroup``s per wrapped operator
(``AbstractWrapperOperator.java:103``) and per-round ``LatencyStats``
(``AbstractPerRoundWrapperOperator.java:106,500-553``), and leans on Flink
metric reporters. The TPU equivalents live here: a metrics registry
(:mod:`flinkml_tpu.utils.metrics`) and the program's spans plus
``jax.profiler`` capture (:mod:`flinkml_tpu.utils.profiling`).
"""

from flinkml_tpu.utils.logging import enable_console, get_logger, rank_tag
from flinkml_tpu.utils.metrics import (
    EpochMetricsListener,
    Meter,
    MetricGroup,
    MetricsRegistry,
    default_registry,
    metrics,
)
from flinkml_tpu.utils.preemption import ElasticResumePlan, PreemptionWatchdog
from flinkml_tpu.utils.profiling import span, trace

__all__ = [
    "EpochMetricsListener",
    "Meter",
    "MetricGroup",
    "MetricsRegistry",
    "default_registry",
    "metrics",
    "span",
    "trace",
    "enable_console",
    "get_logger",
    "rank_tag",
    "PreemptionWatchdog",
    "ElasticResumePlan",
]
