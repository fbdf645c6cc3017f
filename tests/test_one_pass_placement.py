"""The trainers' one-pass placement (``DeviceMesh.shard_rows`` under
``_linear_sgd._place_shuffled`` and ``prepare_sparse_buckets``): the
table reaches the mesh in the seeded row order, chunk by chunk through
rotating staging buffers, and is bit for bit what the full-size host
passes it replaced placed: ``shard_batch(pad(a.astype(dtype)[perm]))``.
That holds for the small columns too (PR 27): the labels and a weight
column go as rows of width ``()``, and the unit weights of a table with
no weight column are made on the device."""

import jax
import numpy as np
import pytest

from flinkml_tpu.models import (
    LinearRegression,
    LinearSVC,
    LogisticRegression,
    _linear_sgd,
)
from flinkml_tpu.parallel import DeviceMesh, mesh as mesh_mod, pad_to_multiple
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils import metrics

DIM = 5
#: Staging bytes that cut a 1003-row table into 10-21 rounds (102 float32
#: or 51 float64 rows a round on one device, 12 or 6 a shard on eight):
#: more rounds than buffers, rows not a multiple of the round.
TINY_STAGE = 2048


def _mesh(devices):
    return DeviceMesh(devices=jax.devices()[:devices])


def _column(kind, rows, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "int64":
        return rng.integers(-9, 9, size=(rows, DIM))
    if kind == "strided":  # every other column of a wider float32 array
        return rng.normal(size=(rows, 2 * DIM)).astype(np.float32)[:, ::2]
    return rng.normal(size=(rows, DIM)).astype(kind)


def _old_pattern(x, y, w, mesh, seed, dtype):
    """What ``train_linear_model`` did before the one-pass placement."""
    if dtype is not None:
        x, y, w = x.astype(dtype), y.astype(dtype), w.astype(dtype)
    perm = np.random.default_rng(seed).permutation(x.shape[0])
    p = mesh.axis_size()
    return tuple(mesh.shard_batch(pad_to_multiple(a[perm], p)[0])
                 for a in (x, y, w))


def _assert_same_placement(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.sharding == want.sharding
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    for g, e in zip(got.addressable_shards, want.addressable_shards):
        assert g.device == e.device and g.index == e.index
        assert np.asarray(g.data).tobytes() == np.asarray(e.data).tobytes()


@pytest.mark.parametrize("stage_bytes", [TINY_STAGE, None],
                         ids=["many-rounds", "one-round"])
@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("rows", [1003, 37])
@pytest.mark.parametrize("dtype", [None, np.float32], ids=["asis", "f32"])
@pytest.mark.parametrize("kind", ["float32", "float64", "int64", "strided"])
def test_one_pass_placement_equals_the_three_host_passes(
        monkeypatch, kind, dtype, rows, devices, stage_bytes):
    if stage_bytes is not None:
        monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", stage_bytes)
    mesh = _mesh(devices)
    x = _column(kind, rows)
    assert x.flags.c_contiguous == (kind != "strided")
    rng = np.random.default_rng(1)
    y, w = rng.integers(0, 2, rows).astype(np.float64), rng.random(rows)
    got = _linear_sgd._placed(
        _linear_sgd._place_shuffled(x, y, w, mesh, 11, dtype))
    want = _old_pattern(x, y, w, mesh, 11, dtype)
    for g, e in zip(got, want):
        _assert_same_placement(g, e)
    if rows % devices:  # rows past the table's end are zero
        tail = np.asarray(got[0])[rows:]
        assert tail.shape[0] == -rows % devices and not tail.any()


@pytest.mark.parametrize("devices", [1, 8])
def test_rounds_rotate_through_the_staging_buffers(monkeypatch, devices):
    """More rounds than buffers: every round waits, gathers and places
    once, and gathers into a buffer only after waiting for the write that
    read it last (``device_put`` does not snapshot a host buffer and may
    alias it; too early a reuse would show as wrong rows only where it
    does, so the order itself is checked here)."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    gathered, waited = [], []  # staging buffer of each round; rounds waited for
    unread = {}  # staging buffer -> the round whose write read it last
    real_writer, real_gather = mesh_mod._row_writer, mesh_mod._gather_rows

    class Token:
        def __init__(self, token, rnd):
            self.token, self.rnd = token, rnd

        def block_until_ready(self):
            self.token.block_until_ready()
            waited.append(self.rnd)

    def writer(*key):
        def write(table, rows, offset):
            table, token = real_writer(*key)(table, rows, offset)
            unread[gathered[-1]] = len(gathered) - 1
            return table, Token(token, len(gathered) - 1)
        return write

    def gather(pool, x, index, out, scratch):
        buffer = (out if out.base is None else out.base).ctypes.data
        if buffer in unread:
            assert unread.pop(buffer) in waited
        gathered.append(buffer)
        real_gather(pool, x, index, out, scratch)

    monkeypatch.setattr(mesh_mod, "_row_writer", writer)
    monkeypatch.setattr(mesh_mod, "_gather_rows", gather)
    mesh, rows = _mesh(devices), 1003
    x = _column("float32", rows)
    perm = np.random.default_rng(3).permutation(rows)
    counters = metrics.group("span")
    before = dict(counters.snapshot()["counters"])
    placed = mesh.shard_rows(x, perm, np.float32)
    after = counters.snapshot()["counters"]
    calls = {k: after[f"{k}.calls"] - before.get(f"{k}.calls", 0)
             for k in ("hostdata.stage_wait", "hostdata.shuffle", "mesh.shard_batch")}
    n_local = -(-rows // devices)
    chunk = TINY_STAGE // (devices * DIM * 4)
    rounds = -(-n_local // chunk)
    assert rounds > mesh_mod._STAGE_BUFFERS
    assert calls == dict.fromkeys(calls, rounds)
    assert len(gathered) == rounds
    assert len(set(gathered)) == mesh_mod._STAGE_BUFFERS
    assert waited == list(range(rounds - mesh_mod._STAGE_BUFFERS))
    sent = (after["mesh.shard_batch.bytes"]
            - before.get("mesh.shard_batch.bytes", 0))
    assert sent == rounds * devices * chunk * DIM * 4
    want = mesh.shard_batch(pad_to_multiple(x[perm], devices)[0])
    _assert_same_placement(placed, want)


def test_a_narrower_training_dtype_is_cast_in_the_staging_pass(monkeypatch):
    """float64 -> float32 goes through one scratch buffer of the source's
    dtype, reused by every round, and rounds as ``astype`` does."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    mesh, rows = _mesh(8), 1003
    x = _column("float64", rows) * 1e-3 + 1.0  # values float32 cannot hold
    perm = np.random.default_rng(5).permutation(rows)
    placed = mesh.shard_rows(x, perm, np.float32)
    assert placed.dtype == np.float32
    want = mesh.shard_batch(pad_to_multiple(x.astype(np.float32)[perm], 8)[0])
    _assert_same_placement(placed, want)


# Coefficients of a PARENT commit for `_seeded_table`, run under
# tests/conftest.py (x64 on, eight CPU devices), as float.hex: the
# unweighted ones of 36477c5 (float64 copy, x[perm], shard_batch), which
# 2ba32a5 reproduced; the weighted ones ("w": column `w`) of 2ba32a5
# (y[perm] and w[perm] on the host, np.ones for no weight column). A fit
# trains on the same bytes in the same rows, so it reproduces them; rtol
# 1e-12 leaves room for another CPU's instruction selection and for
# nothing else (a float32 fit of the same table differs by 1e-8).
PARENT_COEFFICIENTS = {
    ("lr", "float32", None): [
        '0x1.4440df8b07e32p-4', '-0x1.e342b58e80211p-5',
        '0x1.b2c5c99aa2e17p-5', '-0x1.961d7f887d304p-4',
        '-0x1.5578b5f024e47p-3',
    ],
    ("lr", "float64", None): [
        '0x1.4440df8ccc4ffp-4', '-0x1.e342b5c5f8d9ap-5',
        '0x1.b2c5c98387ab7p-5', '-0x1.961d7f82b8ebbp-4',
        '-0x1.5578b5f91d89ap-3',
    ],
    ("softmax", "float32", None): [
        '-0x1.404f3c1d27507p-4', '0x1.dce67373d4e67p-5',
        '-0x1.7d20e896ef245p-5', '0x1.852a0ebeb775fp-4',
        '0x1.3ed82cd659590p-3', '0x1.260a5816e54c7p-8',
        '-0x1.ac28a5a8f3b18p-8', '0x1.8bc2adb31aac4p-8',
        '0x1.1115095f3a69ap-7', '0x1.cdc3557b47a44p-8',
        '0x1.2dee969bb8fbap-4', '-0x1.a7615ebeb6703p-5',
        '0x1.4ba892e08bcedp-5', '-0x1.a74cafea9ec33p-4',
        '-0x1.4d46478233962p-3',
    ],
    ("svc", "float32", None): [
        '0x1.57d9aff4bac78p-3', '-0x1.f06042e8c85d1p-4',
        '0x1.c47adc7035b62p-4', '-0x1.a394b7c91e1edp-3',
        '-0x1.65ce8d99010aap-2',
    ],
    ("linreg-sgd", "float32", None): [
        '0x1.607e3a21a93d8p-2', '-0x1.11ca77bc6d094p-2',
        '0x1.e99aea88609c8p-3', '-0x1.05161793b5494p-1',
        '-0x1.8cc47cd0d7ffdp-1',
    ],
    ("lr", "float32", "w"): [
        '0x1.3f84a62c0b844p-4', '-0x1.0690188687711p-4',
        '0x1.dbe38773890b4p-5', '-0x1.93a125d3f55acp-4',
        '-0x1.514ce93f7af36p-3',
    ],
    ("softmax", "float32", "w"): [
        '-0x1.3a68e11e1e17ap-4', '0x1.0318776c25938p-4',
        '-0x1.a1d0b08218357p-5', '0x1.87bd78cafdcb9p-4',
        '0x1.3ba18cb09b670p-3', '0x1.6c5d214a95042p-9',
        '-0x1.4702662b8bb10p-7', '0x1.87e7254303308p-8',
        '0x1.c8c945bd58162p-8', '0x1.b8f14fed14814p-8',
        '0x1.2f05f813c96f8p-4', '-0x1.b470554d683acp-5',
        '0x1.70d3cbd9b7cf5p-5', '-0x1.a44a0d26d34d1p-4',
        '-0x1.49691730040b0p-3',
    ],
    ("svc", "float32", "w"): [
        '0x1.509729428e3a0p-3', '-0x1.0f6deb7732498p-3',
        '0x1.ef9170817e7f6p-4', '-0x1.a1725e409118ep-3',
        '-0x1.613c3f17c20cbp-2',
    ],
    ("linreg-sgd", "float32", "w"): [
        '0x1.56a1774719a3ep-2', '-0x1.232a4f0367a24p-2',
        '0x1.02829c3689060p-2', '-0x1.0958f202fd99fp-1',
        '-0x1.8a9199c11c053p-1',
    ],
}
# The same for `_seeded_sparse_table` (a `CsrColumn` of dim 512; 2ba32a5),
# where 512 coefficients a case are recorded as three numbers: their sum
# and their products with `_PROBES`. The sparse trainer computes in
# float32, so another CPU may move them in the eighth digit; a label or a
# weight in the wrong row moves them in the second.
PARENT_SPARSE_PROJECTIONS = {
    ("lr", "uniform", None): [
        '0x1.0e95b83090000p-4', '-0x1.508450f18ca34p-7', '-0x1.d802e0a327942p-4'],
    ("lr", "uniform", "w"): [
        '0x1.60ce98ccf8000p-4', '-0x1.7d8a84248e750p-7', '-0x1.f05a7fdf46a72p-4'],
    ("lr", "ragged", None): [
        '0x1.9ca2fb28b0000p-4', '-0x1.4f88afbd4c90cp-5', '-0x1.a386b2bb8a7c3p-5'],
    ("lr", "ragged", "w"): [
        '0x1.93124e9318000p-4', '-0x1.31454c3df0f14p-5', '-0x1.035a7d8aac23ap-5'],
    ("svc", "uniform", None): [
        '0x1.13b127d166000p-3', '-0x1.4e1123b2c40a8p-6', '-0x1.db77f51a960f8p-3'],
    ("svc", "uniform", "w"): [
        '0x1.66d1c4b1b8000p-3', '-0x1.7f9645a8999c0p-6', '-0x1.f46560f0bb080p-3'],
    ("svc", "ragged", None): [
        '0x1.9ee5404a8c000p-3', '-0x1.5219633535381p-4', '-0x1.a5ce3c16587a6p-4'],
    ("svc", "ragged", "w"): [
        '0x1.9570306da0000p-3', '-0x1.336c225225967p-4', '-0x1.0429621ef1f0cp-4'],
    ("linreg-sgd", "uniform", None): [
        '0x1.3f7eb1dd80000p-2', '0x1.91b34ccdf0920p-5', '-0x1.32c236cc51812p+1'],
    ("linreg-sgd", "uniform", "w"): [
        '0x1.449737e7e0000p-1', '0x1.892c217cee200p-6', '-0x1.2af2f813aecc8p+1'],
    ("linreg-sgd", "ragged", None): [
        '0x1.2050e495e4c00p+1', '-0x1.a005cf1084654p-4', '-0x1.83cf2d7f38811p-1'],
    ("linreg-sgd", "ragged", "w"): [
        '0x1.2d55874e40000p+1', '0x1.be645bd8c2bc0p-3', '-0x1.4b710199720b7p-1'],
}

SPARSE_DIM, SPARSE_NNZ = 512, 39
_PROBES = np.random.default_rng(99).normal(size=(2, SPARSE_DIM))


def _seeded_table(dtype, classes=2):
    rng = np.random.default_rng(2025)
    x = rng.normal(size=(1003, DIM)).astype(dtype)
    margin = x.astype(np.float64) @ rng.normal(size=DIM)
    if classes == 2:
        y = (margin > 0).astype(np.float64)
    else:
        y = np.digitize(margin, [-0.5, 0.5]).astype(np.float64)
    return Table({"features": x, "label": y, "target": margin,
                  "w": rng.random(1003) + 0.5, "ones": np.ones(1003)})


def _seeded_sparse_table(uniform):
    rng = np.random.default_rng(2026)
    nnz = (np.full(1003, SPARSE_NNZ) if uniform
           else rng.integers(0, 60, size=1003))
    indptr = np.concatenate([[0], np.cumsum(nnz)]).astype(np.int64)
    indices = np.concatenate(
        [np.sort(rng.choice(SPARSE_DIM, k, replace=False)) for k in nnz]
    ).astype(np.int32)
    values = rng.normal(size=indices.size).astype(np.float32)
    coef = rng.normal(size=SPARSE_DIM)
    margin = np.array([values[a:b] @ coef[indices[a:b]]
                       for a, b in zip(indptr[:-1], indptr[1:])])
    return Table({"features": CsrColumn(indptr, indices, values, SPARSE_DIM),
                  "label": (margin > 0).astype(np.float32), "target": margin,
                  "w": rng.random(1003) + 0.5, "ones": np.ones(1003)})


def _fit_seeded(name, table, weight_col):
    est = {"lr": LogisticRegression, "softmax": LogisticRegression,
           "svc": LinearSVC, "linreg-sgd": LinearRegression}[name]()
    if name == "linreg-sgd":
        est.set_label_col("target")  # solver "sgd", the default
    if weight_col is not None:
        est.set_weight_col(weight_col)
    est.set_max_iter(6).set_global_batch_size(256).set_learning_rate(0.1)
    est.set_seed(7)
    return np.asarray(est.fit(table).coefficient, np.float64)


def _unit_weight_fits():
    return metrics.group("hostdata").snapshot()["counters"].get(
        "unit_weights_on_device", 0.0)


@pytest.mark.parametrize(
    "name,dtype,weights",
    [*PARENT_COEFFICIENTS,
     *((n, d, "ones") for n, d, w in PARENT_COEFFICIENTS if w is None)])
def test_fit_reproduces_the_parent_commits_coefficients(
        monkeypatch, name, dtype, weights):
    """No weight column (unit weights made on the device) and a column of
    ones (gathered and placed like any weights) both give the parent's
    unweighted fit; a real column the parent's weighted one."""
    key = (name, dtype, None if weights == "ones" else weights)
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    want = np.array([float.fromhex(h) for h in PARENT_COEFFICIENTS[key]])
    before = _unit_weight_fits()
    got = _fit_seeded(name, _seeded_table(dtype, 3 if name == "softmax" else 2),
                      weights)
    np.testing.assert_allclose(got.ravel(), want, rtol=1e-12, atol=0)
    assert _unit_weight_fits() - before == (weights is None)


@pytest.mark.parametrize("weights", [None, "ones", "w"])
@pytest.mark.parametrize("rows", ["uniform", "ragged"])
@pytest.mark.parametrize("name", ["lr", "svc", "linreg-sgd"])
def test_sparse_fit_reproduces_the_parent_commits_coefficients(
        monkeypatch, name, rows, weights):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    key = (name, rows, None if weights == "ones" else weights)
    want = [float.fromhex(h) for h in PARENT_SPARSE_PROJECTIONS[key]]
    before = _unit_weight_fits()
    coef = _fit_seeded(name, _seeded_sparse_table(rows == "uniform"), weights)
    got = [coef.sum(), *(_PROBES @ coef)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)
    assert _unit_weight_fits() - before == (weights is None)


# -- the sparse trainer's blocks (PR 26): int32 indices and float32 values,
# two arrays in one row order ------------------------------------------------

def _sparse_rows(rows, uniform, seed=0):
    """CSR of ``rows`` rows (39 cells each, or 0 to 59), labels, weights."""
    rng = np.random.default_rng(seed)
    nnz = (np.full(rows, SPARSE_NNZ) if uniform
           else rng.integers(0, 60, size=rows))
    indptr = np.concatenate([[0], np.cumsum(nnz)]).astype(np.int64)
    indices = np.concatenate(
        [np.sort(rng.choice(SPARSE_DIM, k, replace=False)) for k in nnz]
    ).astype(np.int32)
    values = rng.normal(size=indices.size).astype(np.float32)
    return (indptr, indices, values,
            rng.integers(0, 2, rows).astype(np.float32),
            (rng.random(rows) + 0.5).astype(np.float32))


def _old_sparse_pattern(indptr, indices, values, y, w, mesh, seed, batch):
    """What ``prepare_sparse_buckets`` did before the one-pass placement:
    each bucket's block permuted whole on the host, padded, placed in one
    transfer an array."""
    from flinkml_tpu.ops.sparse import pack_ell_buckets

    p, n = mesh.axis_size(), indptr.size - 1
    buckets, row_ids = pack_ell_buckets(indptr, indices, values, SPARSE_DIM)
    rng = np.random.default_rng(seed)
    placed, sizes = [], []
    for bucket, rows in zip(buckets, row_ids):
        if rows is None:  # one width: the bucket's rows are the table's
            rows = np.arange(n)
        order = rng.permutation(rows.size)
        for a in (bucket["indices"][order], bucket["values"][order],
                  y[rows[order]], w[rows[order]]):
            placed.append(mesh.shard_batch(pad_to_multiple(a, p)[0]))
        share = max(1, -(-batch * rows.size // (n * p)))
        sizes.append(min(share, placed[-1].shape[0] // p))
    return tuple(placed), tuple(sizes)


@pytest.mark.parametrize("stage_bytes", [TINY_STAGE, None],
                         ids=["many-rounds", "one-round"])
@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("rows", [1003, 37])
@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "ragged"])
def test_sparse_blocks_are_placed_as_the_whole_array_passes_placed_them(
        monkeypatch, uniform, rows, devices, stage_bytes):
    if stage_bytes is not None:
        monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", stage_bytes)
    mesh = _mesh(devices)
    indptr, indices, values, y, w = _sparse_rows(rows, uniform)
    place, got_sizes, plan = _linear_sgd.prepare_sparse_buckets(
        indptr, indices, values, SPARSE_DIM, y, w, mesh, 256, seed=11)
    # every window read: the whole table placed
    got = _linear_sgd._placed(place(0, rows))
    if plan:  # the blocks' starts come after the buckets' arrays
        got = got[:-1]
    want, want_sizes = _old_sparse_pattern(
        indptr, indices, values, y, w, mesh, 11, 256)
    assert got_sizes == want_sizes and len(got) == len(want)
    assert len(got) == (4 if uniform else 4 * len(got_sizes))
    assert got[0].dtype == np.int32 and got[1].dtype == np.float32
    for g, e in zip(got, want):
        _assert_same_placement(g, e)


@pytest.mark.parametrize("devices", [1, 8])
def test_an_int32_block_goes_through_the_staging_rounds(monkeypatch, devices):
    """Indices take the dense features' path: rounds through the rotating
    buffers, no cast, no scratch; rows past the table's end are zero."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    mesh, rows = _mesh(devices), 1003
    block = np.random.default_rng(2).integers(
        0, 1 << 20, size=(rows, SPARSE_NNZ)).astype(np.int32)
    perm = np.random.default_rng(3).permutation(rows)
    counters = metrics.group("span")
    before = counters.snapshot()["counters"].get("mesh.shard_batch.calls", 0)
    placed = mesh.shard_rows(block, perm, np.int32)
    rounds = counters.snapshot()["counters"]["mesh.shard_batch.calls"] - before
    chunk = max(1, TINY_STAGE // (devices * SPARSE_NNZ * 4))
    assert rounds == -(-(-(-rows // devices)) // chunk) > mesh_mod._STAGE_BUFFERS
    assert placed.dtype == np.int32
    want = mesh.shard_batch(pad_to_multiple(block[perm], devices)[0])
    _assert_same_placement(placed, want)


# -- the small columns (PR 27): what a fit hands its trainer for labels
# and weights, against shard_batch(pad(a.astype(dtype)[perm])) ---------------

def _fit_table(layout, rows, seed=4):
    """A table of ``rows`` rows with an int64 label column, a column of
    ones and a column of real weights; the features dense, a ``CsrColumn``
    of one width, or a ragged one that packs into several buckets."""
    rng = np.random.default_rng(seed)
    small = {"label": rng.integers(0, 2, rows),
             "ones": np.ones(rows, np.float32),
             "real": rng.random(rows) + 0.5}
    if layout == "dense":
        return Table({"features": _column("float32", rows), **small})
    indptr, indices, values, _, _ = _sparse_rows(rows, layout == "uniform")
    column = CsrColumn(indptr, indices, values, SPARSE_DIM)
    return Table({"features": column, **small})


def _received(monkeypatch, table, weight_col, devices, seed=11):
    """The arrays ``LogisticRegression.fit`` hands the device loop, for
    steps that read every window."""
    seen = []

    def capture(trainer, place, dim, dt, *args, **kwargs):
        seen.append(_linear_sgd._placed(place(0, 1 << 30)))
        return np.zeros(dim, dt)

    monkeypatch.setattr(_linear_sgd, "_run_chunked", capture)
    est = LogisticRegression(mesh=_mesh(devices)).set_seed(seed)
    if weight_col is not None:
        est.set_weight_col(weight_col)
    est.fit(table)
    (data_args,) = seen
    return data_args


@pytest.mark.parametrize("rows", [1000, 1003], ids=["divisible", "ragged-end"])
@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("layout", ["dense", "uniform", "several-buckets"])
@pytest.mark.parametrize("weights", [None, "ones", "real"])
def test_labels_and_weights_reach_the_trainer_as_the_host_passes_placed_them(
        monkeypatch, weights, layout, devices, rows):
    from flinkml_tpu.ops.sparse import pack_ell_buckets

    mesh, table = _mesh(devices), _fit_table(layout, rows)
    y = table.column("label")
    w = np.ones(rows) if weights is None else table.column(weights)
    before = _unit_weight_fits()
    got = _received(monkeypatch, table, weights, devices)
    assert _unit_weight_fits() - before == (weights is None)
    rng = np.random.default_rng(11)
    if layout == "dense":
        dtype, picks = np.float64, [rng.permutation(rows)]
        small = [got[1:3]]
    else:
        column = table.csr_column("features")
        _, row_ids = pack_ell_buckets(
            column.indptr, column.indices, column.values, SPARSE_DIM)
        assert (len(row_ids) > 1) == (layout == "several-buckets")
        dtype, picks = np.float32, []
        for ids in row_ids:  # None: the one bucket's rows are the table's
            ids = np.arange(rows) if ids is None else ids
            picks.append(ids[rng.permutation(ids.size)])
        small = [got[4 * b + 2:4 * b + 4] for b in range(len(row_ids))]
    for picked, (yd, wd) in zip(picks, small):
        for placed, a in ((yd, y), (wd, w)):
            want = mesh.shard_batch(
                pad_to_multiple(a.astype(dtype)[picked], devices)[0])
            _assert_same_placement(placed, want)


def test_a_label_column_goes_through_the_staging_rounds(monkeypatch):
    """A 1-D column is a table of rows of width ``()``: rounds through the
    rotating buffers, cast in the gather, zeros past its end."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", 256)
    mesh, rows = _mesh(8), 1003
    y = np.random.default_rng(2).integers(0, 2, rows)
    perm = np.random.default_rng(3).permutation(rows)
    counters = metrics.group("span")
    before = counters.snapshot()["counters"].get("mesh.shard_batch.calls", 0)
    placed = mesh.shard_rows(y, perm, np.float32)
    rounds = counters.snapshot()["counters"]["mesh.shard_batch.calls"] - before
    assert rounds == -(-126 // (256 // (8 * 4))) > mesh_mod._STAGE_BUFFERS
    want = mesh.shard_batch(pad_to_multiple(y.astype(np.float32)[perm], 8)[0])
    _assert_same_placement(placed, want)
    assert not np.asarray(placed)[rows:].any()


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("rows", [1, 37, 1000, 1003])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_unit_weights_made_on_the_device_are_the_placed_ones(
        dtype, rows, devices):
    mesh = _mesh(devices)
    want = mesh.shard_batch(pad_to_multiple(np.ones(rows, dtype), devices)[0])
    _assert_same_placement(mesh.shard_ones(rows, dtype), want)
