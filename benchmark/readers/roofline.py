"""A kernel's share of its roofline, in %: the least seconds the chip
could take for one unit (``flops_bytes.<params["count"]>`` over the
arguments named in ``params["args"]``, each looked up in the cell's
file, then the configuration's) over the measured device seconds per
unit (as ``trace_busy_per_unit`` reads them)."""

from benchmark import flops_bytes
from benchmark.readers.trace_busy_per_unit import busy_seconds_per_unit


def read(params, obs):
    measured = busy_seconds_per_unit(params, obs)
    if measured is None:
        return None
    lookup = {**obs["config"], **obs["cell"]}
    args = {k: lookup[v] for k, v in params["args"].items()}
    count = getattr(flops_bytes, params["count"])(**args)
    least, _ = flops_bytes.least_seconds(count, obs["peaks"])
    return 100.0 * least / measured
