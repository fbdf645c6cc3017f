"""``profiling.span``: the program's one span facility, and the host
spans of ``LogisticRegression.fit(Table)`` and ``PipelineModel.transform``
that the benchmark's per-layer metrics read (docs/development/
observability.md, "Spans")."""

import contextlib
import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest

from flinkml_tpu import pipeline_fusion
from flinkml_tpu.models import LogisticRegression, _linear_sgd
from flinkml_tpu.models.logistic_regression import LogisticRegressionModel
from flinkml_tpu.models.scalers import MaxAbsScalerModel, StandardScalerModel
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.pipeline import PipelineModel
from flinkml_tpu.table import Table
from flinkml_tpu.utils import metrics, profiling
from flinkml_tpu.utils.profiling import span

# A table of one staging round (DeviceMesh.stage_rows places a fit's
# columns in lockstep): the permutation, then the round's wait, gather and
# placement. The names are the parent's (2ba32a5); with no weight column
# the weights are made on the device and open no span.
FIT_SPANS = {"fit": 1, "hostdata.ingest": 1, "hostdata.shuffle": 2,
             "hostdata.stage_wait": 1, "mesh.shard_batch": 1,
             "trainer.loop": 1, "trainer.readback": 1}
#: The rounds' spans, which ``trainer.loop`` holds (PR 33).
ROUND_SPANS = ("hostdata.stage_wait", "hostdata.shuffle", "mesh.shard_batch")


def _counters(group="span"):
    return dict(metrics.group(group).snapshot()["counters"])


@contextlib.contextmanager
def _delta(group="span"):
    """What the block added to a metric group's counters."""
    before, out = _counters(group), {}
    yield out
    for k, v in _counters(group).items():
        if v != before.get(k, 0.0):
            out[k] = v - before.get(k, 0.0)


def _calls(delta):
    return {k[:-len(".calls")]: v for k, v in delta.items()
            if k.endswith(".calls")}


def test_span_adds_seconds_calls_and_counts():
    with _delta() as d:
        with span("t.basic", bytes=10, rows=2) as s:
            s.add(bytes=5, steps=7)
    assert d["t.basic.calls"] == 1
    assert d["t.basic.bytes"] == 15 and d["t.basic.rows"] == 2
    assert d["t.basic.steps"] == 7
    assert 0 < d["t.basic.seconds"] < 1.0
    assert "t.basic.errors" not in d
    text = metrics.render_text()
    assert 'flinkml_t_basic_seconds{group="span"}' in text


def test_nested_spans_both_count_and_the_child_fits_in_the_parent():
    with _delta() as d:
        with span("t.parent"):
            with span("t.child"):
                sum(range(20000))
            with span("t.child"):
                pass
    assert d["t.parent.calls"] == 1 and d["t.child.calls"] == 2
    assert 0 < d["t.child.seconds"] <= d["t.parent.seconds"]


def test_span_closed_by_an_exception_counts_and_reraises():
    with _delta() as d:
        with pytest.raises(KeyError, match="boom"):
            with span("t.raises", rows=4):
                raise KeyError("boom")
    assert d["t.raises.calls"] == 1 and d["t.raises.errors"] == 1
    assert d["t.raises.rows"] == 4 and d["t.raises.seconds"] > 0


def test_span_as_a_decorator_gives_every_call_its_own_instance():
    @span("t.decorated", rows=1)
    def depth(n):
        return 0 if n == 0 else 1 + depth(n - 1)

    with _delta() as d:
        assert depth(3) == 3
    assert d["t.decorated.calls"] == 4 and d["t.decorated.rows"] == 4


def test_spans_from_many_threads_lose_no_update():
    n_threads, per_thread = 16, 500
    barrier = threading.Barrier(n_threads)

    def work():
        barrier.wait(timeout=30)
        for _ in range(per_thread):
            with span("t.threads", rows=3):
                with span("t.threads.inner"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with _delta() as d:
            threads = [threading.Thread(target=work) for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    total = n_threads * per_thread
    assert d["t.threads.calls"] == total and d["t.threads.rows"] == 3 * total
    assert d["t.threads.inner.calls"] == total


def test_span_is_a_flinkml_annotation_in_a_profile(tmp_path):
    with profiling.trace(str(tmp_path), ignore_errors=False):
        with span("t.visible"):
            with span("t.visible.child"):
                pass
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
             for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith(profiling.SPAN_PREFIX)}
    assert set(found) == {"flinkml:t.visible", "flinkml:t.visible.child"}
    (p0, p1), (c0, c1) = found["flinkml:t.visible"], found["flinkml:t.visible.child"]
    assert p0 <= c0 and c1 <= p1  # nesting on a thread is the parent link


def _lr_table(rows=1003, dim=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, dim)).astype(np.float32)
    y = (x @ rng.normal(size=dim) > 0).astype(np.float64)
    return Table({"features": x, "label": y, "w": rng.random(rows) + 0.5})


def _fit(table, weight_col=None):
    est = (LogisticRegression().set_max_iter(6).set_global_batch_size(256)
           .set_learning_rate(0.5).set_seed(7))
    if weight_col is not None:
        est.set_weight_col(weight_col)
    return np.asarray(est.fit(table).coefficient)


@pytest.mark.parametrize("weight_col", [None, "w"], ids=["unweighted", "weighted"])
@pytest.mark.parametrize("stage_bytes", [None, 4096], ids=["one-round", "many-rounds"])
def test_fit_produces_each_fit_span_once(monkeypatch, stage_bytes, weight_col):
    from flinkml_tpu.parallel import mesh

    p = len(jax.devices())
    n_local = -(-1003 // p)
    width = jax.dtypes.canonicalize_dtype(np.float64).itemsize
    chunk = n_local  # rows a shard sends a round, of every column
    if stage_bytes is not None:
        monkeypatch.setattr(mesh, "_STAGE_BYTES", stage_bytes)
        chunk = stage_bytes // (p * 5 * width)  # by the widest: the features
    rounds = -(-n_local // chunk)
    assert (rounds > 1) == (stage_bytes is not None)
    # the labels, and a weight column the same way; none: made on the device
    small = 1 if weight_col is None else 2
    table = _lr_table()
    with _delta() as d, _delta("hostdata") as made:
        _fit(table, weight_col)
    # Six steps of four windows a shard read every row: every round goes.
    assert _calls(d) == {**FIT_SPANS, "hostdata.shuffle": 1 + rounds,
                         "hostdata.stage_wait": rounds,
                         "mesh.shard_batch": rounds}
    assert made == ({"unit_weights_on_device": 1} if weight_col is None else {})
    # What was placed: the columns in lockstep, round by round (the last
    # round steps back over rows already sent), padded to the mesh, at
    # the width the device holds (float32 where x64 is off).
    assert d["mesh.shard_batch.bytes"] == rounds * chunk * (5 + small) * p * width
    # The phases on the fit's thread add up to it; the rounds lie inside
    # the loop, all but the permutation's share of hostdata.shuffle.
    top = sum(d[f"{k}.seconds"] for k in
              ("hostdata.ingest", "trainer.loop", "trainer.readback"))
    assert top <= d["fit.seconds"]
    assert (d["hostdata.stage_wait.seconds"] + d["mesh.shard_batch.seconds"]
            <= d["trainer.loop.seconds"])
    # seconds and calls apiece, and the one count a metric reads: a name
    # nothing reads is not added, and no name the parent did not have
    assert set(d) == ({f"{s}.{c}" for s in FIT_SPANS for c in ("seconds", "calls")}
                      | {"mesh.shard_batch.bytes"})


def _profiled_spans(tmp_path, run):
    """``(start, end, name)`` of every program span of ``run()`` under a
    profile, in start order."""
    with profiling.trace(str(tmp_path), ignore_errors=False):
        run()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name[len(profiling.SPAN_PREFIX):])
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith(profiling.SPAN_PREFIX))


@pytest.mark.parametrize("stage_bytes", [None, 4096], ids=["one-round", "many-rounds"])
def test_the_loop_holds_the_rounds_spans_and_the_rest_are_siblings(
        monkeypatch, tmp_path, stage_bytes):
    """On the fit's thread: ``hostdata.ingest``, the permutation's
    ``hostdata.shuffle``, ``trainer.loop`` and ``trainer.readback`` one
    after another inside ``fit``; every round's wait, gather and
    placement one after another inside ``trainer.loop`` (PR 33: the loop
    runs on the windows that have landed while the rest are gathered)."""
    from flinkml_tpu.parallel import mesh

    if stage_bytes is not None:
        monkeypatch.setattr(mesh, "_STAGE_BYTES", stage_bytes)
    table = _lr_table()
    _fit(table)  # compiled before the profile
    (fit_start, fit_end, name), *phases = _profiled_spans(
        tmp_path, lambda: _fit(table))
    assert name == "fit" and {n for _, _, n in phases} == set(FIT_SPANS) - {"fit"}
    (loop,) = [(a, b) for a, b, n in phases if n == "trainer.loop"]
    inside = [ph for ph in phases if loop[0] <= ph[0] and ph[1] <= loop[1]
              and ph[2] != "trainer.loop"]
    outside = [ph for ph in phases if ph not in inside]
    assert [n for _, _, n in outside] == [
        "hostdata.ingest", "hostdata.shuffle", "trainer.loop", "trainer.readback"]
    rounds, n_local = len(inside) // 3, -(-1003 // len(jax.devices()))
    width = jax.dtypes.canonicalize_dtype(np.float64).itemsize
    assert rounds == (1 if stage_bytes is None else -(-n_local // (
        stage_bytes // (len(jax.devices()) * 5 * width))))
    assert [n for _, _, n in inside] == list(ROUND_SPANS) * rounds
    for group, (lo, hi) in ((outside, (fit_start, fit_end)), (inside, loop)):
        end = lo
        for start, stop, name in group:
            assert end <= start <= stop <= hi, name
            end = stop


@pytest.mark.parametrize("boundaries", ["pipelined", "listeners"])
def test_no_step_of_a_fit_runs_outside_a_loop_span(monkeypatch, boundaries):
    """Every dispatch of the trainer happens while ``trainer.loop`` is
    open, and what the last one returned is on the host before the span
    closes: the chip's busy time inside the span holds every step, so a
    step read from it cannot come out short (nor a roofline share high)."""
    from flinkml_tpu.parallel import mesh

    monkeypatch.setattr(mesh, "_STAGE_BYTES", 4096)
    open_spans, dispatched, at_close = [], [], []

    class Span(span):
        def __enter__(self):
            open_spans.append(self.name)
            return super().__enter__()

        def __exit__(self, *exc):
            if self.name == "trainer.loop":
                at_close.extend(out[0].is_ready() for _, out in dispatched)
            assert open_spans.pop() == self.name
            return super().__exit__(*exc)

    real = _linear_sgd._dense_trainer

    def dense_trainer(*key):
        trainer = real(*key)

        def run(*args):
            out = trainer(*args)
            dispatched.append((list(open_spans), out))
            return out

        return run

    monkeypatch.setattr(_linear_sgd, "span", Span)
    monkeypatch.setattr(_linear_sgd, "_dense_trainer", dense_trainer)
    table = _lr_table()

    class Listener:
        def on_epoch_watermark_incremented(self, epoch, coef):
            pass

        def on_iteration_terminated(self, coef):
            pass

    listeners = (Listener(),) if boundaries == "listeners" else ()
    _linear_sgd.train_linear_model(
        table.column("features"), table.column("label"), None, "logistic",
        DeviceMesh(), 6, 0.5, 256, 0.0, 0.0, 0.0, 7, dtype=np.float32,
        listeners=listeners)
    assert len(dispatched) == (1 if listeners else 4)
    assert all(stack == ["trainer.loop"] for stack, _ in dispatched)
    assert at_close == [True] * len(dispatched)


def test_the_unit_weights_metric_reads_one_a_fit():
    """``benchmark/metrics/hostdata.unit_weights_on_device_per_fit.json``
    through the benchmark's ``counter_ratio`` reader, over the counters of
    three small fits as ``benchmark/run.py`` flattens them."""
    import json

    from benchmark.readers import counter_ratio

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "metrics",
                           "hostdata.unit_weights_on_device_per_fit.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    table = _lr_table()

    def read(fits, weight_col):
        with _delta("hostdata") as made:
            for _ in range(fits):
                _fit(table, weight_col)
        counters = {f"hostdata.{k}": v for k, v in made.items()}
        return counter_ratio.read(
            spec["params"], {"counters": counters, "setup_counters": {},
                             "units": {"fits": fits}})

    assert read(3, None) == 1.0
    assert read(2, "w") is None  # as on the parent: no count, no metric


def _chain(dim=5, seed=1):
    rng = np.random.default_rng(seed)

    def row(**cols):
        return Table({k: np.asarray(v, np.float64)[None, :] for k, v in cols.items()})

    s1 = StandardScalerModel().set(StandardScalerModel.INPUT_COL, "features") \
        .set(StandardScalerModel.OUTPUT_COL, "s1")
    s1.set_model_data(row(mean=rng.normal(size=dim), std=1 + rng.random(dim)))
    s2 = MaxAbsScalerModel().set(MaxAbsScalerModel.INPUT_COL, "s1") \
        .set(MaxAbsScalerModel.OUTPUT_COL, "s2")
    s2.set_model_data(row(maxAbs=1 + rng.random(dim)))
    lr = LogisticRegressionModel().set(LogisticRegressionModel.FEATURES_COL, "s2")
    lr.set_model_data(row(coefficient=rng.normal(size=dim)))
    return PipelineModel([s1, s2, lr])


def _score(model, table):
    (out,) = model.transform(table)
    return out, np.asarray(out.column("prediction"))


def test_transform_produces_each_transform_span_once():
    model = _chain()
    table = Table({"features": _lr_table(rows=600).column("features")})
    with _delta() as d, _delta("pipeline.fusion") as fusion, _delta("table") as tab:
        _score(model, table)
    assert _calls(d) == {"transform": 1, "table.to_device": 1,
                         "fusion.constants": 1, "fusion.dispatch": 1,
                         "table.to_host": 1}
    assert all(k.endswith((".seconds", ".calls")) for k in d)
    # the bytes either way stay where they were counted before the spans
    assert fusion["host_to_device_bytes"] == 600 * 5 * 4
    assert tab["device_to_host_bytes"] == 600 * 8
    with _delta() as again:
        _score(model, table)  # the Table keeps its device copy
    assert "table.to_device.calls" not in again
    assert _calls(again) == {"transform": 1, "fusion.constants": 1,
                             "fusion.dispatch": 1, "table.to_host": 1}


def test_spans_live_in_their_own_group():
    """The groups other tests snapshot keep exactly the counters they had."""
    model, table = _chain(), Table({"features": _lr_table(rows=64).column("features")})
    with _delta("pipeline.fusion") as fusion, _delta("table") as tab:
        _score(model, table)
    known = {"host_to_device_transfers", "host_to_device_bytes", "compiles",
             "cache_hits", "fused_segments", "fused_stages",
             "host_transfer_bytes_avoided", "aot_loads", "pallas_compiles"}
    assert set(fusion) <= known
    assert set(tab) == {"device_to_host_materializations", "device_to_host_bytes"}
    assert not any(k.endswith((".seconds", ".calls")) for k in (*fusion, *tab))


class _NoSpan:
    """What the parent commit had at every span site: nothing."""

    def __init__(self, *a, **k):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **counts):
        pass


def _without_spans(monkeypatch):
    from flinkml_tpu import pipeline
    from flinkml_tpu.models import _data, logistic_regression
    from flinkml_tpu.parallel import mesh

    for mod in (_data, _linear_sgd, logistic_regression, mesh, pipeline,
                pipeline_fusion, profiling):
        monkeypatch.setattr(mod, "span", _NoSpan)
    _linear_sgd._dense_trainer.cache_clear()
    pipeline_fusion.reset_cache()


def test_spans_change_no_result(monkeypatch):
    table = _lr_table()
    model, rows = _chain(), Table({"features": _lr_table(rows=600).column("features")})
    _linear_sgd._dense_trainer.cache_clear()
    pipeline_fusion.reset_cache()
    coef = _fit(table)
    out, pred = _score(model, rows)
    raw = np.asarray(out.column("rawPrediction"))
    with monkeypatch.context() as m:
        _without_spans(m)
        with _delta() as d:
            coef0 = _fit(table)
            out0, pred0 = _score(model, Table({"features": rows.column("features")}))
            raw0 = np.asarray(out0.column("rawPrediction"))
        assert d == {}  # the parent's path really ran: no span counted
    _linear_sgd._dense_trainer.cache_clear()
    pipeline_fusion.reset_cache()
    assert coef.tobytes() == coef0.tobytes()
    assert pred.tobytes() == pred0.tobytes() and raw.tobytes() == raw0.tobytes()
