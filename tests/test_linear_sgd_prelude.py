"""``_linear_sgd._run_chunked``'s prelude (PR 46): the start carry and the
hyper-parameters are host values of the loop's dtypes until the fit's
dispatch. The carry goes up in ONE ``device_put`` (committed where the
trainer returns it, so every chunk enters the executable the first one
compiled), the scalars ride the dispatch as its operands; no ``jnp.zeros``,
no ``jnp.asarray`` a scalar. The operands' values and dtypes are the
parent's, so every trainer returns the parent's coefficients however the
fit is driven: in one dispatch, in chunks that follow the placement's
rounds, from a checkpoint with ``resume``, with listeners."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flinkml_tpu.iteration import CheckpointManager
from flinkml_tpu.models import LogisticRegression, _linear_sgd
from flinkml_tpu.parallel import DeviceMesh, mesh as mesh_mod
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils import metrics

ROWS, DIM, CLASSES, SPARSE_DIM, NNZ = 1003, 5, 3, 1000, 8
STEPS = 12
#: Staging bytes that cut the 1003-row tables below into many rounds.
TINY_STAGE = 2048
KINDS = ("dense", "sparse", "softmax")

#: What the parent commit (446ceaa, before PR 46) returned for
#: ``_train(kind, four devices)``: every coefficient of the dense and the
#: softmax fit (float64 under the tests' x64), the sparse fit's
#: coefficients' sum and four seeded projections (float32 on the device).
PARENT = {
    "dense": ["0x1.660d2ad52860ap-2", "-0x1.0639f6fa6c7fep+0",
              "-0x1.ebd3f49029c85p-3", "-0x1.c552995c09407p-1",
              "-0x1.8b222ba5c22d3p-4"],
    "sparse": ["0x1.1f4a91d538000p-1", "0x1.0f093e615660ep-3",
               "0x1.806f12f87fb90p-7", "0x1.953267f1b006fp-2",
               "-0x1.e1f299f07313ap-3"],
    "softmax": ["0x1.b9192506bedf9p-1", "-0x1.c7fcf47a55bbep-2",
                "-0x1.91dd8b09602bbp-2", "-0x1.b9fa0bc319708p-6",
                "-0x1.0c972ea08ece9p-6", "-0x1.b4f7c528f97cap-2",
                "0x1.b43cd99d52e87p-1", "-0x1.d71cf7546cce9p-2",
                "-0x1.d5d09f5d706ecp-8", "-0x1.bb7fcd7653f41p-9",
                "-0x1.bcfd0d50b1684p-2", "-0x1.a03f472c7d3adp-2",
                "0x1.b49bfcf8cfea5p-1", "0x1.18ad292d1af65p-5",
                "0x1.440672ce721edp-6"],
}
_PROBES = np.random.default_rng(5).normal(size=(4, SPARSE_DIM))


def _mesh(devices=4):
    return DeviceMesh(devices=jax.devices()[:devices])


def _arrays(kind):
    rng = np.random.default_rng(11)
    if kind == "sparse":
        indptr = np.arange(ROWS + 1, dtype=np.int64) * NNZ
        indices = np.concatenate(
            [np.sort(rng.choice(SPARSE_DIM, NNZ, replace=False))
             for _ in range(ROWS)]).astype(np.int32)
        values = rng.normal(size=indices.size).astype(np.float32)
        coef = rng.normal(size=SPARSE_DIM)
        margin = (values * coef[indices]).reshape(ROWS, NNZ).sum(axis=1)
        return (indptr, indices, values), (margin > 0).astype(np.float32)
    x = rng.normal(size=(ROWS, DIM))
    if kind == "softmax":
        return x, np.argmax(x[:, :CLASSES] + 0.3 * rng.normal(size=(ROWS, CLASSES)),
                            axis=1).astype(np.float64)
    return x, (x @ rng.normal(size=DIM) > 0).astype(np.float64)


def _train(kind, mesh, max_iter=STEPS, **kwargs):
    """``kind``'s trainer on arrays (no table: nothing is kept), no weights."""
    x, y = _arrays(kind)
    hyper = dict(mesh=mesh, max_iter=max_iter, learning_rate=0.5,
                 global_batch_size=128, reg=0.01, tol=0.0, seed=7, **kwargs)
    if kind == "sparse":
        return _linear_sgd.train_linear_model_sparse_csr(
            *x, SPARSE_DIM, y, None, loss="logistic", elastic_net=0.5, **hyper)
    if kind == "softmax":
        return _linear_sgd.train_softmax_model(
            x, y, None, num_classes=CLASSES, elastic_net=0.5, **hyper)
    return _linear_sgd.train_linear_model(
        x, y, None, "logistic", elastic_net=0.5, **hyper)


def _counted(name):
    return metrics.group("trainer").snapshot()["counters"].get(name, 0.0)


class _Recorder:
    def __init__(self):
        self.heard = []

    def on_epoch_watermark_incremented(self, epoch, coef):
        self.heard.append(("watermark", epoch, np.array(coef)))

    def on_iteration_terminated(self, coef):
        self.heard.append(("terminated", None, np.array(coef)))


def _assert_the_parents(kind, coef):
    want = np.array([float.fromhex(h) for h in PARENT[kind]])
    coef = np.asarray(coef, np.float64).ravel()
    if kind == "sparse":  # float32 on the device: as test_one_pass_placement
        got = np.array([coef.sum(), *(_PROBES @ coef)])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)
    else:
        np.testing.assert_allclose(coef, want, rtol=1e-12, atol=0)


@pytest.fixture(scope="module")
def one_dispatch():
    """Each trainer's fit in ONE dispatch (the table is one round)."""
    mesh = _mesh()
    return {kind: _train(kind, mesh) for kind in KINDS}


@pytest.mark.parametrize("kind", KINDS)
def test_a_fit_in_one_dispatch_returns_the_parents_coefficients(
        one_dispatch, kind):
    coef = one_dispatch[kind]
    assert coef.dtype == (np.float32 if kind == "sparse" else np.float64)
    assert coef.shape == {"dense": (DIM,), "sparse": (SPARSE_DIM,),
                          "softmax": (CLASSES, DIM)}[kind]
    _assert_the_parents(kind, coef)


@pytest.mark.parametrize("kind", KINDS)
def test_a_fit_in_several_chunk_dispatches_is_the_one_dispatch_fit(
        monkeypatch, one_dispatch, kind):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    before = _counted("pipelined_steps")
    got = _train(kind, _mesh())
    assert _counted("pipelined_steps") > before  # several dispatches
    assert got.tobytes() == one_dispatch[kind].tobytes()
    _assert_the_parents(kind, got)


@pytest.mark.parametrize("kind", KINDS)
def test_a_resumed_checkpointed_fit_is_the_one_dispatch_fit(
        tmp_path, one_dispatch, kind):
    """The restored host carry takes the road the zeros take."""
    mesh, manager = _mesh(), CheckpointManager(str(tmp_path))
    _train(kind, mesh, 8, checkpoint_manager=manager, checkpoint_interval=4)
    assert manager.latest_epoch() == 8
    before = _counted("steps")
    got = _train(kind, mesh, checkpoint_manager=manager, checkpoint_interval=3,
                 resume=True)
    assert _counted("steps") - before == STEPS - 8
    assert manager.latest_epoch() == STEPS
    assert got.tobytes() == one_dispatch[kind].tobytes()
    _assert_the_parents(kind, got)
    # and an uninterrupted fit in the manager's chunks
    fresh = CheckpointManager(str(tmp_path / "whole"))
    whole = _train(kind, mesh, checkpoint_manager=fresh, checkpoint_interval=5)
    assert whole.tobytes() == one_dispatch[kind].tobytes()


@pytest.mark.parametrize("kind", KINDS)
def test_a_fit_with_listeners_is_the_one_dispatch_fit(one_dispatch, kind):
    recorder = _Recorder()
    got = _train(kind, _mesh(), listeners=(recorder,))
    assert got.tobytes() == one_dispatch[kind].tobytes()
    _assert_the_parents(kind, got)
    assert [(what, epoch) for what, epoch, _ in recorder.heard] == [
        ("watermark", STEPS - 1), ("terminated", None)]
    assert all(coef.tobytes() == got.tobytes() for _, _, coef in recorder.heard)


_FACTORIES = {"dense": "_dense_trainer", "sparse": "_sparse_trainer_bucketed",
              "softmax": "_softmax_trainer"}


def _seen_trainers(monkeypatch, kind, on_dispatch=None):
    """Every jitted trainer ``kind``'s factory hands out from here on."""
    real, seen = getattr(_linear_sgd, _FACTORIES[kind]), []

    def factory(*key):
        trainer = real(*key)
        seen.append(trainer)
        if on_dispatch is None:
            return trainer

        def run(*args):
            on_dispatch(args)
            return trainer(*args)

        return run

    real.cache_clear()
    monkeypatch.setattr(_linear_sgd, _FACTORIES[kind], factory)
    return seen


@pytest.mark.parametrize("kind", KINDS)
def test_a_multi_chunk_fit_holds_one_executable(monkeypatch, kind):
    """The first chunk is entered from the carry as ``device_put`` laid
    it, the later ones from the carry the chunk before returned: one
    signature to ``jax.jit``, one executable."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    dispatches = []
    seen = _seen_trainers(monkeypatch, kind, dispatches.append)
    _train(kind, _mesh())
    assert len(dispatches) > 1
    assert len(set(map(id, seen))) == 1 and seen[0]._cache_size() == 1
    # What rides every dispatch: five host scalars of the loop's dtypes.
    dt = np.float32 if kind == "sparse" else np.float64
    for args in dispatches:
        *hy, end = args[-5:]
        assert all(type(v) is np.ndarray and v.shape == () and v.dtype == dt
                   for v in hy)
        assert type(end) is np.int32
    # the first carry as the later ones: on the mesh, replicated
    first, later = dispatches[0][:3], dispatches[-1][:3]
    assert all(isinstance(a, jax.Array) for a in first + later)
    assert [a.sharding for a in first] == [a.sharding for a in later]
    assert [a.dtype for a in first] == [a.dtype for a in later] == [
        dt, np.int32, dt]


def _table(kind):
    x, y = _arrays(kind)
    if kind == "sparse":
        x = CsrColumn(*x, SPARSE_DIM)
    return Table({"features": x, "label": y})


@pytest.mark.parametrize("kind", KINDS)
def test_a_hit_makes_one_host_to_device_call_before_its_dispatch(
        monkeypatch, kind):
    """A fit that finds its placement kept with its table: the carry's one
    ``device_put``, then the dispatch."""
    table, mesh = _table(kind), _mesh()

    def fit():
        return np.asarray(LogisticRegression(mesh=mesh).set_max_iter(STEPS)
                          .set_global_batch_size(128).set_seed(7)
                          .fit(table).coefficient)

    want = fit()  # the miss: places the table, keeps the placement
    calls, at_dispatch = [], []
    for module, name in ((jax, "device_put"), (jnp, "asarray"), (jnp, "array"),
                         (jnp, "zeros"), (jnp, "full")):
        def counting(*args, _real=getattr(module, name), _name=name, **kwargs):
            calls.append(_name)
            return _real(*args, **kwargs)

        monkeypatch.setattr(module, name, counting)
    _seen_trainers(monkeypatch, kind, lambda args: at_dispatch.append(list(calls)))
    hits = metrics.group("hostdata").snapshot()["counters"]["placement_hits"]
    got = fit()
    assert metrics.group("hostdata").snapshot()["counters"][
        "placement_hits"] == hits + 1
    assert at_dispatch == [["device_put"]]
    assert got.tobytes() == want.tobytes()
