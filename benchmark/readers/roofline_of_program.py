"""A kernel's share of its roofline, in %, with the kernel's time read
off the program that holds it: the least seconds the chip could take for
one unit (``<params["module"]>.<params["count"]>`` of the ``benchmark``
package over the arguments named in ``params["args"]``, each looked up
in the cell's file, then the configuration's) over the device seconds
per unit of the programs ``params["programs"]`` (as
``trace_program_device_time`` reads them).
``readers/roofline_in_program_span.py`` with the time's source swapped:
what the named program ran, not what the chip did while the host was
inside a span."""

import importlib

from benchmark import flops_bytes
from benchmark.readers.trace_program_device_time import device_seconds_per_unit


def read(params, obs):
    measured = device_seconds_per_unit(params, obs)
    if measured is None:
        return None
    lookup = {**obs["config"], **obs["cell"]}
    args = {k: lookup[v] for k, v in params["args"].items()}
    counts = importlib.import_module(f"benchmark.{params['module']}")
    count = getattr(counts, params["count"])(**args)
    least, _ = flops_bytes.least_seconds(count, obs["peaks"])
    return 100.0 * least / measured
