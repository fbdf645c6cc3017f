"""Tracing / profiling: ``jax.profiler`` integration + device-accurate timers.

SURVEY.md §5 "Tracing / profiling": the reference relies on Flink operator
metrics and latency markers; the TPU equivalent is ``jax.profiler`` traces
(viewable in XProf/TensorBoard) plus per-step wall timing that accounts for
JAX's async dispatch. These helpers degrade gracefully: if the profiler
cannot start (e.g. unsupported on the backend), ``trace`` becomes a no-op
rather than failing the training job.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax

from flinkml_tpu.utils.metrics import MetricGroup


@contextlib.contextmanager
def trace(log_dir: str, ignore_errors: bool = True) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace of the enclosed block into ``log_dir``.

    Usage::

        with trace("/tmp/jax-trace"):
            model = estimator.fit(train_table)
    """
    try:
        jax.profiler.start_trace(log_dir)
        started = True
    except Exception:
        if not ignore_errors:
            raise
        started = False
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                if not ignore_errors:
                    raise


def annotate(name: str):
    """Named region visible in profiler timelines (host + device).

    Thin alias of ``jax.profiler.TraceAnnotation`` usable as a context
    manager or decorator.
    """
    return jax.profiler.TraceAnnotation(name)


class StepTimer:
    """Device-accurate step timing under async dispatch.

    ``jit`` calls return before the device finishes; naive wall-clock
    timing measures dispatch, not execution. ``StepTimer`` blocks on the
    step's outputs (``block_until_ready``) before reading the clock and optionally records into a metric group::

        timer = StepTimer(group=metrics.group("train"))
        for batch in data:
            with timer:
                state = step(state, batch)
                timer.observe(state)   # block target
    """

    def __init__(self, group: Optional[MetricGroup] = None,
                 series: str = "step_seconds"):
        self.group = group
        self.series = series
        self.times = []
        self._pending = None
        self._t0 = 0.0

    def observe(self, value) -> None:
        """Register the step output to block on at exit."""
        self._pending = value

    def __enter__(self) -> "StepTimer":
        self._pending = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None and self._pending is not None:
            jax.block_until_ready(self._pending)
        dt = time.perf_counter() - self._t0
        self.times.append(dt)
        if self.group is not None:
            self.group.record(self.series, dt)

    @property
    def mean(self) -> float:
        return sum(self.times) / len(self.times) if self.times else 0.0
