"""The benchmark's own tests: run from ``benchmark/`` with
``python -m pytest tests -q`` (not part of the repo's tier-1). They run
on the CPU: every size is a rehearsal size, and no number they produce
is a device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
