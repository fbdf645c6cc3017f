"""The Pallas kernels' shared layer and the top-k kernel: interpret-mode
parity of ``topk`` against ``lax.top_k`` (and through KNN and LSH), its
refusals, what :mod:`flinkml_tpu.kernels._mosaic` gives every kernel
(``interpret_mode``, ``out_struct``), that no switch between lowerings
is left in the package (PR 57), and that the kernels' listings name the
kernels that are there."""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from flinkml_tpu import kernels, pipeline_fusion
from flinkml_tpu.kernels import (
    ENV_INTERPRET_VAR, KernelUnsupportedError, _mosaic, topk,
)
from flinkml_tpu.table import Table
from tests.test_docs_switches import PACKAGE, REPO, package_sources


@pytest.fixture
def fusion_cache():
    pipeline_fusion.reset_cache()
    saved = list(pipeline_fusion.on_compile)
    yield
    pipeline_fusion.on_compile[:] = saved
    pipeline_fusion.reset_cache()


# -- top-k parity ------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "float64", "bfloat16"])
def test_top_k_parity(dtype):
    """Values AND indices bitwise vs ``lax.top_k``, including ties
    (both break toward the lower index) and a row count that is not a
    multiple of the kernel's row tile."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(13, 57))).astype(dtype)
    x = x.at[0, 9].set(x[0, 3])   # tie inside one row
    x = x.at[5, :].set(x[5, 0])   # fully tied row
    rv, ri = jax.lax.top_k(x, 6)
    pv, pi = topk.pallas_top_k(x, 6)
    assert np.asarray(rv).tobytes() == np.asarray(pv).tobytes()
    assert np.asarray(ri).tobytes() == np.asarray(pi).tobytes()


def test_top_k_neg_inf_rows_parity():
    """A row whose tail is -inf must walk the untaken -inf entries in
    ascending index order exactly like ``lax.top_k`` — masking the
    selected column cannot alias the remaining -inf entries (the
    duplicate-index regression)."""
    x = jnp.asarray([
        [-np.inf, 5.0, -np.inf],
        [-np.inf, -np.inf, -np.inf],
        [1.0, -np.inf, 2.0],
    ], dtype=jnp.float32)
    rv, ri = jax.lax.top_k(x, 3)
    pv, pi = topk.pallas_top_k(x, 3)
    assert np.asarray(rv).tobytes() == np.asarray(pv).tobytes()
    assert np.asarray(ri).tobytes() == np.asarray(pi).tobytes()


def test_top_k_1d_parity():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=41).astype(np.float32))
    rv, ri = jax.lax.top_k(x, 7)
    pv, pi = topk.pallas_top_k(x, 7)
    assert np.asarray(rv).tobytes() == np.asarray(pv).tobytes()
    assert np.asarray(ri).tobytes() == np.asarray(pi).tobytes()


def test_knn_backends_agree(fusion_cache, monkeypatch):
    """KNN predictions are the same whichever way a tile is ranked,
    ``lax.top_k`` (every backend but a TPU) or the kernel (a TPU;
    interpreted here): the vote consumes only the top-k indices, which
    are bitwise-equal."""
    from flinkml_tpu.models import knn
    from flinkml_tpu.models.knn import Knn

    rng = np.random.default_rng(6)
    x = rng.normal(size=(80, 4))
    y = (x[:, 0] > 0).astype(np.float64)
    t = Table({"features": x, "label": y})
    model = Knn().set(Knn.FEATURES_COL, "features") \
        .set(Knn.LABEL_COL, "label").set(Knn.K, 5).fit(t)
    q = Table({"features": rng.normal(size=(30, 4))})
    (ref,) = model.transform(q)
    ranked_by_kernel = []

    def by_kernel(d2, k):
        neg, at = topk.pallas_top_k(-d2, k, interpret=True)
        ranked_by_kernel.append(d2.shape)
        return -neg, at

    monkeypatch.setattr(knn, "_tile_top_k", by_kernel)
    # Another query count: a new trace, which sees the patched ranking.
    q2 = Table({"features": np.asarray(q.column("features"))[:29]})
    (got,) = model.transform(q2)
    assert ranked_by_kernel
    assert np.array_equal(np.asarray(ref.column("prediction"))[:29],
                          np.asarray(got.column("prediction")))


def test_lsh_ranking_pinned_order(monkeypatch):
    """The satellite fix (lsh.py host argsort → device top_k): ranking
    order equals the stable host argsort EXACTLY — ascending distance,
    ties toward the lower candidate index — through the model
    (``lax.top_k``) and with the kernel ranking the same distances."""
    from flinkml_tpu.models.lsh import MinHashLSH

    rng = np.random.default_rng(7)
    # Low-cardinality 0/1 rows manufacture many EQUAL Jaccard distances,
    # so a tie-break regression cannot hide.
    x = (rng.random((60, 12)) > 0.5).astype(np.float64)
    t = Table({"f": x})
    model = MinHashLSH().set(MinHashLSH.INPUT_COL, "f") \
        .set(MinHashLSH.OUTPUT_COL, "h") \
        .set(MinHashLSH.NUM_HASH_TABLES, 3).set_seed(11).fit(t)

    def golden(key, k):
        """The pre-fix host ranking, reproduced inline."""
        from flinkml_tpu.models.lsh import (
            _active_indices, _jaccard_distance,
        )
        rows = _active_indices(t.column("f"))
        hashes = model._hash_rows(rows)
        key_idx = np.nonzero(np.asarray(key, dtype=np.float64))[0]
        key_hash = model._hash_rows([key_idx])[0]
        cand = np.nonzero((hashes == key_hash[None, :]).any(axis=1))[0]
        dists = np.asarray([
            _jaccard_distance(rows[i], key_idx) for i in cand
        ])
        order = np.argsort(dists, kind="stable")[:k]
        return cand[order], dists[order], dists, order

    for k in (3, 7, 1000):   # 1000 > candidate count: clamp path
        want_rows, want_dists, dists, want_order = golden(x[0], k)
        got = model.approx_nearest_neighbors(t, x[0], k)
        assert np.array_equal(np.asarray(got.column("distCol")),
                              want_dists), k
        assert np.array_equal(np.asarray(got.column("f")),
                              x[want_rows]), k
        # The kernel over the same float64 distances: the same order.
        with jax.enable_x64(True):
            _, by_kernel = topk.pallas_top_k(
                jnp.asarray(-dists), min(k, dists.size))
        assert np.array_equal(np.asarray(by_kernel), want_order), k
        # duplicate distances must actually occur for the tie pin to
        # mean anything
    assert len(np.unique(golden(x[0], 1000)[1])) < \
        len(golden(x[0], 1000)[1])


# -- refusal -----------------------------------------------------------------


def test_top_k_refuses_integer_dtype():
    with pytest.raises(KernelUnsupportedError, match="not floating"):
        topk.pallas_top_k(jnp.arange(10), 3)


def test_top_k_refuses_bad_k():
    x = jnp.ones((4, 8), jnp.float32)
    with pytest.raises(KernelUnsupportedError, match="outside"):
        topk.pallas_top_k(x, 9)


# -- what every kernel shares -------------------------------------------------


@pytest.mark.parametrize("value", ["unset", "0", "1", "bogus"])
def test_interpret_mode_reads_its_variable(value, monkeypatch):
    """Unset: interpreted on every backend but a TPU. ``0``/``1`` force
    it either way; anything else is refused by the variable's name."""
    if value == "unset":
        monkeypatch.delenv(ENV_INTERPRET_VAR, raising=False)
        assert kernels.interpret_mode() is (jax.default_backend() != "tpu")
        return
    monkeypatch.setenv(ENV_INTERPRET_VAR, value)
    if value == "bogus":
        with pytest.raises(ValueError, match=ENV_INTERPRET_VAR):
            kernels.interpret_mode()
    else:
        assert kernels.interpret_mode() is (value == "1")


@pytest.mark.parametrize("where", ["outside", "inside-shard_map"])
def test_out_struct_carries_the_operands_axes(where):
    """A ``pallas_call``'s declared output varies over the union of its
    operands' manual mesh axes: none outside ``shard_map``, the sharded
    operand's inside one (a replicated operand adds none)."""
    seen = []

    def body(varying, replicated):
        seen.append(_mosaic.out_struct((4, 2), jnp.float32, varying, replicated))
        seen.append(_mosaic.out_struct([3], jnp.int32, replicated))
        return varying

    x, r = jnp.ones((8, 2), jnp.float32), jnp.ones(3, jnp.float32)
    if where == "outside":
        body(x, r)
        want = [frozenset(), frozenset()]
    else:
        mesh = Mesh(np.array(jax.devices()), ("data",))
        jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                              out_specs=P("data")))(x, r)
        want = [frozenset({"data"}), frozenset()]
    assert [s.vma for s in seen] == want
    assert [(s.shape, s.dtype) for s in seen] == [
        ((4, 2), jnp.float32), ((3,), jnp.int32)]


# -- one lowering a site ------------------------------------------------------


def test_no_backend_switch_is_left():
    """The package holds no choice between an XLA and a Pallas lowering
    of one site: no ``FLINKML_TPU_KERNELS`` variable but the
    interpreter's, no ``segsum_backend`` parameter, no ``kernel_backend_``
    knob (code or committed table), none of the gate's functions or its
    counter, and the kernels import nothing from ``autotune``."""
    gone = re.compile(
        r"segsum_backend|kernel_backend_|FLINKML_TPU_KERNELS(?!_INTERPRET)"
        r"|resolve_backend|backend_for|refuse_or_fallback|pallas_compiles")
    sources = dict(package_sources())
    table = os.path.join(PACKAGE, "autotune", "tuning_table.json")
    with open(table) as f:
        sources[table] = f.read()
    found = {os.path.relpath(path, REPO): sorted(set(gone.findall(text)))
             for path, text in sources.items() if gone.search(text)}
    assert found == {}
    above = {os.path.relpath(path, REPO) for path, text in sources.items()
             if os.path.dirname(path) == os.path.join(PACKAGE, "kernels")
             and "autotune" in text}
    assert above == set()
    assert not {"segsum", "chain", "_gate"} & set(_kernel_modules(all_=True))


# -- the listings -------------------------------------------------------------


def _kernel_modules(all_=False):
    """The modules of ``flinkml_tpu/kernels/``: all of them, or those
    that hold a kernel (an ``unsupported_reason`` its caller reads, and
    ``spd_solve``, which every backend and shape can run)."""
    names = []
    for path, text in sorted(package_sources().items()):
        if os.path.dirname(path) != os.path.join(PACKAGE, "kernels"):
            continue
        name = os.path.basename(path)[:-3]
        if all_ or "\ndef unsupported_reason(" in text or name == "spd_solve":
            names.append(name)
    return names


def _listings():
    """Where the kernels are listed: the package's docstring, the
    developer document outside its record of what went, the README."""
    with open(os.path.join(REPO, "docs", "development", "kernels.md")) as f:
        document = f.read()
    document = re.sub(r"\n## What went, and why\n.*?(?=\n## |\Z)", "\n",
                      document, flags=re.S)
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    return {"kernels.md": document, "kernels/__init__.py": kernels.__doc__,
            "README.md": readme}


@pytest.mark.parametrize("module", _kernel_modules())
def test_a_running_kernel_is_named_where_the_kernels_are_listed(module):
    listings = _listings()
    for where in ("kernels.md", "kernels/__init__.py"):
        assert re.search(rf"kernels[./]{module}\b", listings[where]), where


def test_no_listing_names_a_kernel_that_is_gone():
    assert len(_kernel_modules()) == 9
    there = set(_kernel_modules(all_=True)) | set(dir(kernels)) | {"md", "py"}
    named = re.compile(r"(?<!\w)(?:flinkml_tpu[./])?kernels[./]([A-Za-z_]+)")
    listings = _listings()
    gone = {where: sorted(set(named.findall(text)) - there)
            for where, text in listings.items()}
    assert gone == {where: [] for where in gone}
    for where, text in listings.items():
        assert not re.search(r"\b_gate\b|gated site|kernel gate", text), where
