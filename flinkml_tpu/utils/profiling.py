"""Tracing: the program's one span facility and ``jax.profiler`` capture.

SURVEY.md §5 "Tracing / profiling": the reference relies on Flink operator
metrics and latency markers. Here :func:`span` is the ONLY way the program
records a span: a host phase that is both a ``jax.profiler``
``TraceAnnotation`` (so it lies on the device trace's clock, over the
chip's ``XLA Ops`` rows) and a few counters in ``metrics.group("span")``
(so it is read with no profiler at all). :func:`trace` captures a profile
and degrades gracefully: if the profiler cannot start (e.g. unsupported
on the backend) it becomes a no-op rather than failing the training job.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

import jax

from flinkml_tpu.utils.metrics import metrics

#: Prefix of every span's ``TraceAnnotation`` in a profile.
SPAN_PREFIX = "flinkml:"
#: The metric group the spans count into.
SPAN_GROUP = "span"


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


class span(contextlib.ContextDecorator):
    """One host phase of the program: ``with span("mesh.shard_batch",
    bytes=n):`` (or ``@span("name")`` around a function).

    - In a profile it is the ``TraceAnnotation`` ``flinkml:<name>`` on
      ``/host:CPU``, on the same clock as the device's operations;
      nesting on a thread is the parent link.
    - On exit it adds to ``metrics.group("span")`` the counters
      ``<name>.seconds`` (host ``perf_counter``), ``<name>.calls``, one
      ``<name>.<key>`` per count passed here or through :meth:`add`
      inside the block, and ``<name>.errors`` if an exception closed it
      (the exception propagates).

    Always on: no flag, no level. With no profiler running a span costs
    two clock reads, one ``TraceMe`` activity check and one locked add
    per counter.
    It adds NO synchronisation: where the enclosed call returns before
    its device work is done (an asynchronous upload or dispatch), the
    span ends when the host was released, not when the chip was.

    A new span comes with the metric or runbook line that reads it
    (``docs/development/observability.md``).
    """

    def __init__(self, name: str, **counts: float):
        self.name = name
        self._counts = counts
        self._annotation = None
        self._t0 = 0.0

    def _recreate_cm(self) -> "span":
        # As a decorator every call gets its own instance, so recursive
        # and concurrent calls of the decorated function do not share
        # a start time.
        return span(self.name, **self._counts)

    def add(self, **counts: float) -> None:
        """Add to this span's counts from inside the block (a size known
        only once the work is done)."""
        for key, value in counts.items():
            self._counts[key] = self._counts.get(key, 0.0) + value

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        group, name = metrics.group(SPAN_GROUP), self.name
        group.counter(f"{name}.seconds", seconds)
        group.counter(f"{name}.calls")
        for key, value in self._counts.items():
            group.counter(f"{name}.{key}", float(value))
        if exc_type is not None:
            group.counter(f"{name}.errors")
