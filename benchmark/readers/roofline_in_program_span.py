"""A kernel's share of its roofline, in %, with the kernel's time scoped
to the program's own span: the least seconds the chip could take for one
unit (``<params["module"]>.<params["count"]>`` of the ``benchmark``
package over the arguments named in ``params["args"]``, each looked up
in the cell's file, then the configuration's) over the device seconds
per unit inside ``params["span"]`` (as ``trace_busy_in_program_span``
reads them). ``readers/roofline.py`` with two differences: the count's
module is named (it looks in ``flops_bytes`` only), and the time is the
program span's, not the benchmark unit's."""

import importlib

from benchmark import flops_bytes
from benchmark.readers.trace_busy_in_program_span import busy_seconds_per_unit


def read(params, obs):
    measured = busy_seconds_per_unit(params, obs)
    if measured is None:
        return None
    lookup = {**obs["config"], **obs["cell"]}
    args = {k: lookup[v] for k, v in params["args"].items()}
    counts = importlib.import_module(f"benchmark.{params['module']}")
    count = getattr(counts, params["count"])(**args)
    least, _ = flops_bytes.least_seconds(count, obs["peaks"])
    return 100.0 * least / measured
