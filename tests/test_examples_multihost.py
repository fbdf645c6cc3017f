"""IT: the user-facing multi-host pod recipe actually runs as a 2-process
Gloo pod (VERDICT r2 item 7 'done' criterion).

The reference's analog is its MiniCluster system tests exercising the
multi-worker control plane (``SharedProgressAligner.java:127-158``,
SURVEY.md §4 tier 3).
"""

import os
import subprocess
import sys


def _run_example(name, args, token):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    example = os.path.join(repo_root, "examples", name)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # Share the suite's persistent XLA cache (see test_distributed.py).
    from flinkml_tpu.utils import jax_cache

    env.setdefault("JAX_COMPILATION_CACHE_DIR", jax_cache.cache_dir())
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    out = subprocess.run(
        [sys.executable, example, *args],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert token in out.stdout, out.stdout


def test_multihost_pod_example_local_demo():
    _run_example("multihost_pod.py", ["--local-demo"], "LOCAL DEMO OK")


def test_multihost_streamed_fit_example_local_demo():
    """The round-4 multi-process streamed-fit recipe: 2 hosts, disjoint
    stream partitions, identical fitted models."""
    _run_example("multihost_streamed_fit.py", [], "local demo OK")
