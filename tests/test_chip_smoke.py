"""``chip_smoke.py`` off the chip: the rehearsal runs every phase on the
8-device CPU mesh, the default invocation refuses a non-TPU backend, and
the one-compile-cache rule holds (``flinkml_tpu.utils.jax_cache`` is the
only setter; ``JAX_COMPILATION_CACHE_DIR`` wins, ``<checkout>/.jax_cache``
otherwise)."""

import json
import os
import re
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chip_smoke lives at the root
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, smoke=SMOKE, **env):
    """Run from a foreign cwd (the script must find its own checkout)."""
    return subprocess.run(
        [sys.executable, smoke, *args], cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600,
        env={**os.environ, **env},
    )


def _checkout_of_its_own(tmp_path):
    """A checkout whose ``.jax_cache`` no one else writes: the script
    copied, what it imports linked. ``jax_cache.cache_dir()`` resolves
    the default from the package's path as imported, so this checkout's
    default is ``<here>/.jax_cache``, not the one the suite's other
    workers fill while this test runs."""
    here = tmp_path / "checkout"
    here.mkdir()
    shutil.copy(SMOKE, here / "chip_smoke.py")
    for name in ("flinkml_tpu", "benchmark", "__graft_entry__.py"):
        os.symlink(os.path.join(REPO, name), here / name)
    return here


def test_rehearsal_runs_every_phase(tmp_path):
    import chip_smoke

    cache = tmp_path / "jaxcache"
    here = _checkout_of_its_own(tmp_path)
    proc = _run(["--rehearse"], tmp_path, smoke=str(here / "chip_smoke.py"),
                JAX_COMPILATION_CACHE_DIR=str(cache))
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()]
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    assert lines[0]["phase"] == "start"
    assert lines[0]["compile_cache_dir"] == str(cache)
    phases = [l for l in lines[1:-2]]
    assert [l["phase"] for l in phases] == [n for n, _ in chip_smoke.PHASES]
    for l in lines[:-1]:
        assert l["rehearsal"] is True and l["platform"] == "cpu", l
    assert all(l["ok"] for l in phases)
    by = {l["phase"]: l for l in phases}
    for name in ("train_dense", "train_sparse", "multichip"):
        assert by[name]["devices_used"] == 8
    assert by["serve"]["replica_device_ids"] == list(range(8))
    assert by["serve"]["compile_cache"]["retarget_loads"] >= 7
    assert by["kernels"]["interpret"] is True
    # The variable was set: the cache went there, and the checkout's own
    # default directory was never made.
    assert any(f.endswith("-cache") for f in os.listdir(cache))
    assert not (here / ".jax_cache").exists()


def test_default_invocation_refuses_a_non_tpu_backend(tmp_path):
    proc = _run([], tmp_path, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout == ""  # no phase line, no result line
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_cache_rule_without_the_variable(monkeypatch):
    from flinkml_tpu.utils import jax_cache

    monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    assert jax_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
    assert jax_cache.aot_dir() == os.path.join(REPO, ".jax_cache", "aot")
    monkeypatch.setenv(jax_cache.ENV_VAR, "/somewhere/else")
    assert jax_cache.cache_dir() == "/somewhere/else"
    assert jax_cache.aot_dir() == "/somewhere/else/aot"


def test_exactly_one_module_sets_the_cache_dir():
    setters = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs  # git-ignored trees hold copies
                   if d not in ("build", "chiprun_out", "__pycache__")
                   and not d.startswith(".")]
        for f in files:
            if not f.endswith((".py", ".sh")):
                continue
            path = os.path.join(root, f)
            if path == os.path.abspath(__file__):
                continue
            with open(path, errors="replace") as fh:
                if re.search(r"jax_compilation_cache_dir", fh.read()):
                    setters.append(os.path.relpath(path, REPO))
    assert setters == [os.path.join("flinkml_tpu", "utils", "jax_cache.py")]
