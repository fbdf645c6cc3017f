"""Tracing: the program's one span facility and ``jax.profiler`` capture.

SURVEY.md §5 "Tracing / profiling": the reference relies on Flink operator
metrics and latency markers. Here :func:`span` is the ONLY way the program
records a span: a host phase that is both a ``jax.profiler``
``TraceAnnotation`` (so it lies on the device trace's clock, over the
chip's ``XLA Ops`` rows) and a few counters in ``metrics.group("span")``
(so it is read with no profiler at all). :func:`named_program` gives a
jitted program the name a profile's ``XLA Modules`` row shows for each of
its runs on the chip, and :func:`phase` is the ONLY way the program names
a part of such a program: the device-side counterpart of a span, read off
the device trace as that part's own device time. :func:`trace` captures a
profile and degrades gracefully: if the profiler cannot start (e.g. unsupported
on the backend) it becomes a no-op rather than failing the training job.
"""

from __future__ import annotations

import contextlib
import threading
import time
import zlib
from typing import Callable, Iterator, Sequence

import jax

from flinkml_tpu.utils.metrics import metrics

#: Prefix of every span's ``TraceAnnotation`` in a profile.
SPAN_PREFIX = "flinkml:"
#: The metric group the spans count into.
SPAN_GROUP = "span"
#: Prefix of a phase's component in an operation's ``op_name`` path
#: (``jit(w2v_sgns_loop)/while/body/flinkml.w2v.draw/sort``): a reader
#: finds the phase without knowing the names.
PHASE_PREFIX = "flinkml."

# The spans open on this thread, outermost first: a span's parent is the
# one under it. ``.phase`` is the phase open on this thread, if any.
_OPEN = threading.local()

try:
    # The profiler's own activity flag (False with no session, True
    # between start_trace and stop_trace, whoever started it). Private to
    # jaxlib, so imported here alone: where a jaxlib lacks it no span
    # writes a ``traced_*`` counter.
    from jax._src.lib import _profiler

    _recording = _profiler.TraceMe.is_enabled
    _recording()
except Exception:  # noqa: BLE001 — any jaxlib without the flag
    def _recording() -> bool:
        return False


def named_program(name: str, fn: Callable, phases: Sequence[str] = ()) -> Callable:
    """``fn`` under the name ``jax.jit`` takes its module's from
    (``jit_<name>``: the event of each of its runs on a profile's ``XLA
    Modules`` row, and the first line of its lowered text). Called on the
    function a program is built from, before ``shard_map`` or ``jit``
    wraps it; ``fn`` itself is renamed, so hand it a function built for
    that one program. The name is part of the compile cache's key.

    ``phases`` declares the :func:`phase` names ``fn`` opens. The cache
    keys a program with its debug information stripped, a phase's name
    with it, so the declaration is made part of the module's name
    (``jit_<name>.<digits>``, a checksum of the names; a profile's reader
    takes ``.<digits>`` off again): an executable compiled before a phase
    existed or was renamed is then another key and is never loaded for
    this program. ``docs/development/observability.md`` ("Programs",
    "Phases") lists the names."""
    if phases:
        name = f"{name}.{zlib.crc32(' '.join(phases).encode())}"
    fn.__name__ = fn.__qualname__ = name
    return fn


@contextlib.contextmanager
def phase(name: str) -> Iterator[None]:
    """One part of a compiled program: ``with phase("w2v.draw"):`` around
    the lines that compute it, inside the function a
    :func:`named_program` is built from (which declares it). Every
    operation traced inside carries ``flinkml.<name>`` in its ``op_name``
    path, loop bodies and kernels included, and a traced run's profile
    gives the device time of the phase's operations
    (``benchmark/readers/trace_phase_device_time``).

    It is ``jax.named_scope`` and nothing else: no counter, no operation,
    no synchronisation, and it runs only while JAX traces the function,
    once a compile. Phases do not nest (``RuntimeError`` at trace time):
    an operation is in one phase or none, and a program's phases add up
    with its unphased time to its device time.

    A new phase comes with the metric that reads it
    (``docs/development/observability.md``)."""
    inside = getattr(_OPEN, "phase", None)
    if inside is not None:
        raise RuntimeError(
            f"phase {name!r} opened inside phase {inside!r}: phases do not nest")
    _OPEN.phase = name
    try:
        with jax.named_scope(PHASE_PREFIX + name):
            yield
    finally:
        _OPEN.phase = None


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
      ``<name>.seconds`` (host ``perf_counter``), ``<name>.self_seconds``
      (its seconds less those of the spans that closed directly under it
      on this thread: over a tree of spans on one thread the self seconds
      add up to the root's seconds), ``<name>.calls``, one
      ``<name>.<key>`` per count passed here or through :meth:`add`
      inside the block, and ``<name>.errors`` if an exception closed it
      (the exception propagates).
    - Where a profiler was recording when it opened or closed, it adds
      the same three again as ``<name>.traced_seconds``,
      ``.traced_self_seconds`` and ``.traced_calls``: ``seconds`` less
      ``traced_seconds`` is the time of the spans no profiler saw.

    Always on: no flag, no level. With no profiler running a span costs
    two clock reads, a push and a pop of this thread's list of open
    spans, one ``TraceMe`` activity check, two reads of the profiler's
    flag and one locked add per counter.
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
        self._children = 0.0
        self._traced = False

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
        try:
            _OPEN.spans.append(self)
        except AttributeError:
            _OPEN.spans = [self]
        self._children = 0.0
        self._traced = _recording()
        self._annotation = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        open_spans = _OPEN.spans
        open_spans.pop()
        if open_spans:
            open_spans[-1]._children += seconds
        own = seconds - self._children
        group, name = metrics.group(SPAN_GROUP), self.name
        group.counter(f"{name}.seconds", seconds)
        group.counter(f"{name}.self_seconds", own)
        group.counter(f"{name}.calls")
        if self._traced or _recording():
            group.counter(f"{name}.traced_seconds", seconds)
            group.counter(f"{name}.traced_self_seconds", own)
            group.counter(f"{name}.traced_calls")
        for key, value in self._counts.items():
            group.counter(f"{name}.{key}", float(value))
        if exc_type is not None:
            group.counter(f"{name}.errors")
