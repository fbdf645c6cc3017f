"""What a recording profiler adds to one call of the span
``params["span"]``, in seconds: its ``traced_seconds`` over its
``traced_calls`` (the calls a profiler saw) less its plain seconds over
its plain calls (``span_plain_seconds.plain``), from the program's
counters over the whole window. Pointed at a unit's root span (``fit``,
``transform``) it is what tracing costs a unit when it is on. None where
the program writes no ``traced_*`` fields, or the window held no traced
call or no plain one."""

from benchmark.readers.span_plain_seconds import plain


def read(params, obs):
    counters, span = obs["counters"], params["span"]
    traced_calls = counters.get(f"span.{span}.traced_calls")
    plain_calls = plain(counters, span, "calls")
    if not traced_calls or not plain_calls:
        return None
    return (counters[f"span.{span}.traced_seconds"] / traced_calls
            - plain(counters, span, "seconds") / plain_calls)
