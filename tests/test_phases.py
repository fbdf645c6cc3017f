"""``profiling.phase``: the names the six hot programs give the parts of a
step (docs/development/observability.md "Phases").

Each program at the small shapes of ``test_spans._lowered_programs``, on
the CPU: every phase it declares is the scope of an instruction of its
COMPILED text (and no other phase is there), the names change nothing
that runs (the optimized module has as many instructions with ``phase``
a null context), a phase inside a phase raises, and a compile cache that
holds the program from before its phases existed does not hide them.
"""

import contextlib
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from flinkml_tpu.models import (
    _als_blocked, _fm_sparse, _gbt_table, _linear_sgd, _mlp_table, _w2v_table, kmeans)
from flinkml_tpu.utils import jax_cache, profiling

from .test_spans import _lowered_programs, _struct

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _w2v_sorted():
    """``w2v_sgns_loop`` as a TPU runs it: the updates in sorted order
    (the kernel interpreted here)."""
    u32, u16, f32, i32 = jnp.uint32, jnp.uint16, jnp.float32, jnp.int32
    d = _w2v_table.Draw(64, 2, 3, 128, 1000, 4096)
    return _w2v_table._program(d, None, True).lower(
        _struct((56, 128), f32), _struct((56, 128), f32),
        _struct((10, 128), i32), _struct((10, 128), u16), _struct((4096,), i32),
        _struct((), u32), _struct((), f32), _struct((), i32))


def _lr_blocked():
    """``lr_sparse_loop`` under a slot plan: one blocked slot (XLA's
    products here) beside a general one."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flinkml_tpu.parallel import DeviceMesh

    mesh = DeviceMesh().mesh
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    f32, i32 = jnp.float32, jnp.int32
    p = len(jax.devices())
    trainer = _linear_sgd._sparse_trainer_bucketed(
        mesh, "logistic", (8,), "data", 300, (128, None))
    return trainer.lower(
        _struct((300,), f32, rep), _struct((), i32, rep), _struct((), f32, rep),
        _struct((128, 2), i32, rows), _struct((128, 2), f32, rows),
        _struct((128,), f32, rows), _struct((128,), f32, rows),
        _struct((2 * p,), i32, rows),
        *[_struct((), f32, rep)] * 4, _struct((), i32, rep))


#: case -> (how to lower it, the phases it declares, the module whose
#: ``phase`` it opens, the cached builder behind it).
CASES = {
    "w2v_sgns_loop": (lambda: _lowered_programs()["w2v_sgns_loop"](),
                      _w2v_table.PHASES_UNSORTED, _w2v_table, _w2v_table._program),
    "w2v_sgns_loop.sorted": (_w2v_sorted, _w2v_table.PHASES, _w2v_table,
                             _w2v_table._program),
    "fm_adam_loop": (lambda: _lowered_programs()["fm_adam_loop"](),
                     _fm_sparse.PHASES, _fm_sparse, _fm_sparse._trainer),
    "als_half_step": (lambda: _lowered_programs()["als_half_step"](),
                      _als_blocked.PHASES, _als_blocked, _als_blocked._program),
    "gbt_forest": (lambda: _lowered_programs()["gbt_forest"](),
                   _gbt_table.PHASES, _gbt_table, _gbt_table._program),
    "lr_sparse_loop": (lambda: _lowered_programs()["lr_sparse_loop"](),
                       _linear_sgd.SPARSE_PHASES, _linear_sgd,
                       _linear_sgd._sparse_trainer_bucketed),
    "lr_sparse_loop.blocked": (_lr_blocked, _linear_sgd.SPARSE_PHASES,
                               _linear_sgd, _linear_sgd._sparse_trainer_bucketed),
    "kmeans_lloyd": (lambda: _lowered_programs()["kmeans_lloyd"](),
                     kmeans.PHASES, kmeans, kmeans._kmeans_trainer),
    "mlp_fit": (lambda: _lowered_programs()["mlp_fit"](),
                _mlp_table.PHASES, _mlp_table, _mlp_table._trainer),
}

_PHASE_IN_PATH = re.compile(
    r'op_name="[^"]*?/' + re.escape(profiling.PHASE_PREFIX) + r'([a-z0-9_.]+?)[/"]')


def _compiled_text(lower) -> str:
    # Past the persistent cache: it keys a module without its debug
    # information, so it may hold this one under other names, or none.
    with jax_cache.suspended():
        return lower().compile().as_text()


def _instructions(text: str) -> int:
    return sum(1 for line in text.splitlines() if " = " in line)


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_declared_phase_scopes_an_instruction_of_the_compiled_program(case):
    lower, declared, _, _ = CASES[case]
    text = _compiled_text(lower)
    assert set(_PHASE_IN_PATH.findall(text)) == set(declared)
    # The declaration is in the module's name, which the compile cache keys.
    program = case.split(".")[0]
    assert re.match(rf"HloModule jit_{program}\.\d+,", text)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_names_change_nothing_the_chip_runs(case, monkeypatch):
    lower, _, module, builder = CASES[case]
    named = _compiled_text(lower)
    builder.cache_clear()
    monkeypatch.setattr(module, "phase", lambda name: contextlib.nullcontext())
    try:
        bare = _compiled_text(lower)
    finally:
        builder.cache_clear()
    assert not _PHASE_IN_PATH.findall(bare)
    assert _instructions(bare) == _instructions(named)


def test_a_phase_is_a_named_scope_and_adds_no_operation():
    def f(x):
        with profiling.phase("probe.a"):
            y = jnp.sin(x)
        return y + 1

    def g(x):
        return jnp.sin(x) + 1

    x = jnp.ones((4,), jnp.float32)
    assert str(jax.make_jaxpr(f)(x)) == str(jax.make_jaxpr(g)(x))
    text = _compiled_text(lambda: jax.jit(f).lower(x))
    assert set(_PHASE_IN_PATH.findall(text)) == {"probe.a"}


def test_a_phase_inside_a_phase_raises_and_leaves_none_open():
    def f(x):
        with profiling.phase("probe.a"):
            with profiling.phase("probe.b"):
                return x + 1

    with pytest.raises(RuntimeError, match="probe.b.*inside.*probe.a"):
        jax.make_jaxpr(f)(1.0)
    # The failed trace closed its phase: one after another is no nesting.
    with profiling.phase("probe.a"):
        pass
    with profiling.phase("probe.b"):
        pass


def test_the_declared_phases_are_in_the_modules_name():
    def one():
        return None

    assert profiling.named_program("p", one).__name__ == "p"
    a = profiling.named_program("p", one, phases=("x.a", "x.b")).__name__
    b = profiling.named_program("p", one, phases=("x.a", "x.c")).__name__
    assert re.fullmatch(r"p\.\d+", a) and re.fullmatch(r"p\.\d+", b) and a != b


_CACHE_SCRIPT = textwrap.dedent("""
    import contextlib, json, sys
    import jax, jax.numpy as jnp
    from flinkml_tpu.utils import jax_cache, profiling

    jax_cache.enable()
    phases = tuple(sys.argv[1:])
    scope = profiling.phase(phases[0]) if phases else contextlib.nullcontext()

    def f(x):
        with scope:
            return jnp.sin(x) * 2.0

    x = jax.block_until_ready(jnp.ones((8,), jnp.float32))
    hits = []
    jax.monitoring.register_event_listener(
        lambda name, **_: hits.append(name)
        if name == "/jax/compilation_cache/cache_hits" else None)
    program = jax.jit(profiling.named_program("cache_probe", f, phases))
    text = program.lower(x).compile().as_text()
    print(json.dumps({"hits": len(hits), "text": text}))
""")


def test_a_cache_from_before_a_phase_existed_does_not_hide_it(tmp_path):
    """JAX keys its persistent cache on the module with the debug
    information stripped: without the declaration in the module's name
    the second process would load the first's executable, names and all."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}

    def run(*phases):
        done = subprocess.run(
            [sys.executable, "-c", _CACHE_SCRIPT, *phases], env=env, cwd=ROOT,
            capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr
        return json.loads(done.stdout.splitlines()[-1])

    assert not _PHASE_IN_PATH.findall(run()["text"])
    # The cache does hold it: the same program again is a hit.
    assert run()["hits"] == 1
    second = run("probe.cached")
    assert second["hits"] == 0
    assert set(_PHASE_IN_PATH.findall(second["text"])) == {"probe.cached"}


def test_named_scope_is_profilings_alone():
    found = []
    for dirpath, _, files in os.walk(os.path.join(ROOT, "flinkml_tpu")):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    if "named_scope" in f.read():
                        found.append(os.path.relpath(path, ROOT))
    assert found == [os.path.join("flinkml_tpu", "utils", "profiling.py")]


def _declared():
    return {
        "w2v_sgns_loop": _w2v_table.PHASES, "fm_adam_loop": _fm_sparse.PHASES,
        "als_half_step": _als_blocked.PHASES, "gbt_forest": _gbt_table.PHASES,
        "lr_sparse_loop": _linear_sgd.SPARSE_PHASES, "kmeans_lloyd": kmeans.PHASES,
        "mlp_fit": _mlp_table.PHASES}


def test_the_docs_phases_table_lists_every_phase_with_the_metric_that_reads_it():
    with open(os.path.join(ROOT, "docs", "development", "observability.md")) as f:
        doc = f.read()
    table = doc[doc.index("### Phases"):]
    rows = re.findall(r"^\| `([a-z0-9_]+)` \| `([a-z0-9_.]+)` \|.*\| `([a-z0-9_.]+)` \|$",
                      table, flags=re.M)
    listed = {}
    for program, phase, _ in rows:
        listed.setdefault(program, []).append(phase)
    assert {k: tuple(v) for k, v in listed.items()} == _declared()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"] for m in json.load(f)["per_layer"]}
    for _, phase, metric in rows:
        assert metric in per_layer, (phase, metric)
        with open(os.path.join(ROOT, "benchmark", "metrics", f"{metric}.json")) as f:
            assert json.load(f)["params"]["phase"] == phase
