"""``profiling.span``: the program's one span facility, and the host
spans of ``LogisticRegression.fit(Table)`` and ``PipelineModel.transform``
that the benchmark's per-layer metrics read (docs/development/
observability.md, "Spans")."""

import contextlib
import glob
import os
import re
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
# columns in lockstep): the permutation (``hostdata.permute`` inside a
# ``hostdata.shuffle`` of its own), then the round's wait, gather and
# placement. With no weight column the weights are made on the device and
# open no span.
FIT_SPANS = {"fit": 1, "hostdata.ingest": 1, "hostdata.shuffle": 2,
             "hostdata.permute": 1,
             "hostdata.stage_wait": 1, "mesh.shard_batch": 1,
             "trainer.loop": 1, "trainer.readback": 1}
#: What every span adds with no profiler recording.
PLAIN_FIELDS = ("seconds", "self_seconds", "calls")
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
    # the table's first fit: its placement is a miss, and is kept (PR 37);
    # so are the label facts its ingest made (PR 46)
    assert made == {"placement_misses": 1, "label_facts_made": 1, **(
        {"unit_weights_on_device": 1} if weight_col is None else {})}
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
    # The permutation is all of the hostdata.shuffle it sits in, so the
    # gather is that span's self time.
    assert d["hostdata.permute.seconds"] <= d["hostdata.shuffle.seconds"]
    # seconds, self seconds and calls apiece (no profiler: no traced_*),
    # and the one count a metric reads: a name nothing reads is not added
    assert set(d) == ({f"{s}.{c}" for s in FIT_SPANS for c in PLAIN_FIELDS}
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
    _fit(_lr_table())  # compiled before the profile, on a table of its own
    (fit_start, fit_end, name), *phases = _profiled_spans(
        tmp_path, lambda: _fit(table))
    assert name == "fit" and {n for _, _, n in phases} == set(FIT_SPANS) - {"fit"}
    (loop,) = [(a, b) for a, b, n in phases if n == "trainer.loop"]
    inside = [ph for ph in phases if loop[0] <= ph[0] and ph[1] <= loop[1]
              and ph[2] != "trainer.loop"]
    outside = [ph for ph in phases if ph not in inside]
    # (the permutation's own span starts inside its hostdata.shuffle)
    assert [n for _, _, n in outside if n != "hostdata.permute"] == [
        "hostdata.ingest", "hostdata.shuffle", "trainer.loop", "trainer.readback"]
    (permute,) = [ph for ph in outside if ph[2] == "hostdata.permute"]
    shuffle = next(ph for ph in outside if ph[2] == "hostdata.shuffle")
    assert shuffle[0] <= permute[0] <= permute[1] <= shuffle[1]
    outside.remove(permute)
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

    def read(fits, weight_col, table=None):
        with _delta("hostdata") as made:
            for _ in range(fits):
                _fit(table or _lr_table(), weight_col)
        counters = {f"hostdata.{k}": v for k, v in made.items()}
        return counter_ratio.read(
            spec["params"], {"counters": counters, "setup_counters": {},
                             "units": {"fits": fits}})

    assert read(3, None) == 1.0  # a table of its own a fit
    assert read(2, "w") is None  # as on the parent: no count, no metric
    # one table: its first fit made the weights, the next two found them
    assert read(3, None, _lr_table()) == 1 / 3


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
    assert all(k.endswith((".seconds", ".self_seconds", ".calls")) for k in d)
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
             "host_transfer_bytes_avoided", "aot_loads"}
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
        assert d == {}  # no span counted: no seconds, no self or traced_* field
    _linear_sgd._dense_trainer.cache_clear()
    pipeline_fusion.reset_cache()
    assert coef.tobytes() == coef0.tobytes()
    assert pred.tobytes() == pred0.tobytes() and raw.tobytes() == raw0.tobytes()


# -- the span tree: parent links as self time, the profiler's flag ---------

def _burn(n=20000):
    return sum(range(n))


def _self_sum(d):
    return sum(v for k, v in d.items() if k.endswith(".self_seconds")
               and not k.endswith(".traced_self_seconds"))


def test_self_seconds_of_a_three_level_tree_sum_to_the_roots_seconds():
    with _delta() as d:
        with span("t.tree.root"):
            _burn()
            with span("t.tree.mid"):
                _burn()
                with span("t.tree.leaf"):
                    _burn()
    assert d["t.tree.leaf.self_seconds"] == d["t.tree.leaf.seconds"]
    assert d["t.tree.mid.self_seconds"] == pytest.approx(
        d["t.tree.mid.seconds"] - d["t.tree.leaf.seconds"], abs=1e-12)
    # only the DIRECT child is taken from the root, not the grandchild again
    assert d["t.tree.root.self_seconds"] == pytest.approx(
        d["t.tree.root.seconds"] - d["t.tree.mid.seconds"], abs=1e-12)
    assert all(d[f"t.tree.{n}.self_seconds"] > 0 for n in ("root", "mid", "leaf"))
    assert _self_sum(d) == pytest.approx(d["t.tree.root.seconds"], abs=1e-12)


def test_siblings_and_repeated_children_all_come_off_the_parent():
    with _delta() as d:
        with span("t.sib.root"):
            for _ in range(3):
                with span("t.sib.a"):
                    _burn()
            with span("t.sib.b"):
                with span("t.sib.a"):  # the same name one level further down
                    _burn()
    assert d["t.sib.a.calls"] == 4 and d["t.sib.b.calls"] == 1
    assert 0 < d["t.sib.root.self_seconds"] < d["t.sib.root.seconds"]
    assert 0 < d["t.sib.b.self_seconds"] < d["t.sib.b.seconds"]
    assert d["t.sib.a.self_seconds"] == d["t.sib.a.seconds"]
    assert _self_sum(d) == pytest.approx(d["t.sib.root.seconds"], abs=1e-12)


def test_a_child_on_another_thread_is_not_taken_from_this_threads_parent():
    def child():
        with span("t.thread.child"):
            _burn(200000)

    with _delta() as d:
        with span("t.thread.parent"):
            worker = threading.Thread(target=child)
            worker.start()
            worker.join(timeout=60)
    assert d["t.thread.child.seconds"] > 0
    # the worker's span is a root of its own thread's tree
    assert d["t.thread.child.self_seconds"] == d["t.thread.child.seconds"]
    assert d["t.thread.parent.self_seconds"] == d["t.thread.parent.seconds"]
    assert d["t.thread.parent.seconds"] >= d["t.thread.child.seconds"]


def test_an_exception_in_a_child_pops_it_and_the_parents_self_time_is_right():
    with _delta() as d:
        with span("t.exc.root"):
            with pytest.raises(KeyError):
                with span("t.exc.child"):
                    _burn()
                    raise KeyError("boom")
            with span("t.exc.after"):  # a sibling, not a child of the dead one
                _burn()
    assert d["t.exc.child.errors"] == 1 and "t.exc.root.errors" not in d
    assert d["t.exc.after.self_seconds"] == d["t.exc.after.seconds"]
    assert d["t.exc.child.self_seconds"] == d["t.exc.child.seconds"]
    assert d["t.exc.root.self_seconds"] == pytest.approx(
        d["t.exc.root.seconds"] - d["t.exc.child.seconds"]
        - d["t.exc.after.seconds"], abs=1e-12)
    assert profiling._OPEN.spans == []


def test_decorated_and_recursive_spans_keep_the_tree():
    @span("t.rec")
    def depth(n):
        _burn(2000)
        return 0 if n == 0 else 1 + depth(n - 1)

    with _delta() as d:
        with span("t.rec.root"):
            assert depth(3) == 3
    assert d["t.rec.calls"] == 4
    # each level's seconds hold the levels under it; their self seconds do not
    assert d["t.rec.self_seconds"] < d["t.rec.seconds"]
    assert _self_sum(d) == pytest.approx(d["t.rec.root.seconds"], abs=1e-12)
    assert d["t.rec.root.self_seconds"] > 0


def test_traced_fields_count_only_the_spans_a_profiler_saw(tmp_path):
    def tree():
        with span("t.traced.root"):
            with span("t.traced.child"):
                _burn()

    with _delta() as plain:
        tree()
    assert not any(".traced_" in k for k in plain)
    with _delta() as d:
        tree()
        with _delta() as seen:
            with profiling.trace(str(tmp_path), ignore_errors=False):
                tree()
                tree()
        with _delta() as after:
            tree()
    assert not any(".traced_" in k for k in after)  # stop_trace ends it
    for name in ("t.traced.root", "t.traced.child"):
        assert d[f"{name}.calls"] == 4 and d[f"{name}.traced_calls"] == 2
        for field in ("seconds", "self_seconds"):
            assert seen[f"{name}.traced_{field}"] == seen[f"{name}.{field}"]
            # what is left when the traced spans are taken off: the plain ones'
            assert d[f"{name}.{field}"] - d[f"{name}.traced_{field}"] == pytest.approx(
                d[f"{name}.{field}"] - seen[f"{name}.{field}"], abs=1e-12)
    assert d["t.traced.root.traced_self_seconds"] == pytest.approx(
        d["t.traced.root.traced_seconds"] - d["t.traced.child.traced_seconds"],
        abs=1e-12)


def test_a_span_open_when_the_profiler_starts_or_stops_counts_as_traced(tmp_path):
    with _delta() as d:
        with span("t.edge.opens"):
            jax.profiler.start_trace(str(tmp_path))
        with span("t.edge.closes"):
            jax.profiler.stop_trace()
        with span("t.edge.later"):
            pass
    assert d["t.edge.opens.traced_calls"] == 1
    assert d["t.edge.closes.traced_calls"] == 1
    assert "t.edge.later.traced_calls" not in d


def test_a_jaxlib_without_the_flag_writes_no_traced_field(monkeypatch, tmp_path):
    monkeypatch.setattr(profiling, "_recording", lambda: False)
    with _delta() as d:
        with profiling.trace(str(tmp_path), ignore_errors=False):
            with span("t.noflag"):
                pass
    assert set(d) == {f"t.noflag.{f}" for f in PLAIN_FIELDS}


def _sparse_table(rows=1003, fields=6, stratum=64, seed=0):
    from flinkml_tpu.table import CsrColumn

    rng = np.random.default_rng(seed)
    indices = (rng.integers(0, stratum, (rows, fields))
               + np.arange(fields) * stratum).astype(np.int32).reshape(-1)
    indptr = (np.arange(rows + 1) * fields).astype(np.int64)
    values = rng.normal(size=indices.size).astype(np.float32)
    return Table({"features": CsrColumn(indptr, indices, values, fields * stratum),
                  "label": rng.integers(0, 2, rows).astype(np.float32)})


@pytest.mark.parametrize("kind", ["dense", "sparse"])
@pytest.mark.parametrize("stage_bytes", [None, 4096], ids=["one-round", "many-rounds"])
def test_a_whole_fits_tree_adds_up_to_the_fit(monkeypatch, kind, stage_bytes):
    """The fit's thread opens every span of a fit, so their self seconds
    are a split of ``fit.seconds``: nothing counted twice (the rounds lie
    inside ``trainer.loop``), nothing negative."""
    from flinkml_tpu.parallel import mesh

    if stage_bytes is not None:
        monkeypatch.setattr(mesh, "_STAGE_BYTES", stage_bytes)
    make = _lr_table if kind == "dense" else _sparse_table
    _fit(make())  # compiled first, on a table of its own
    with _delta() as d:
        _fit(make())
    names = {k[:-len(".calls")] for k in d if k.endswith(".calls")}
    assert names >= set(FIT_SPANS) and (kind == "dense") == (
        "hostdata.sparse_pack" not in names)
    assert all(d[f"{n}.self_seconds"] >= 0 for n in names)
    assert d["fit.self_seconds"] > 0
    assert _self_sum(d) == pytest.approx(d["fit.seconds"], rel=1e-9)
    # one permutation a fit (one a bucket), inside a hostdata.shuffle
    assert d["hostdata.permute.calls"] == 1
    assert d["hostdata.shuffle.calls"] == 1 + d["hostdata.stage_wait.calls"]
    assert d["hostdata.shuffle.seconds"] >= d["hostdata.permute.seconds"]
    assert d["hostdata.shuffle.self_seconds"] == pytest.approx(
        d["hostdata.shuffle.seconds"] - d["hostdata.permute.seconds"], abs=1e-12)
    # the loop's own time: what the rounds' three spans leave of it
    assert 0 < d["trainer.loop.self_seconds"] <= (
        d["trainer.loop.seconds"] - d["hostdata.stage_wait.seconds"]
        - d["mesh.shard_batch.seconds"])


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_a_fit_that_finds_its_placement_opens_no_placement_span(kind):
    """The second fit of a table (PR 37): no permutation, no pack, no
    round; the ingest, the loop's one dispatch and the read-back are the
    fit, and the tree still adds up. ``hostdata`` counts the hit, and its
    gauge holds what the table keeps."""
    table = _lr_table() if kind == "dense" else _sparse_table()
    kept = metrics.group("hostdata").snapshot()["gauges"].get(
        "placement_kept_bytes", 0.0)
    with _delta("hostdata") as first:
        _fit(table)
    assert first["placement_misses"] == 1 and "placement_hits" not in first
    assert first["label_facts_made"] == 1 and "label_facts_kept" not in first
    (entry,) = [v for k, v in table._device_cache.items() if isinstance(k, tuple)]
    gauges = metrics.group("hostdata").snapshot()["gauges"]
    assert gauges["placement_kept_bytes"] == kept + sum(
        a.nbytes for a in entry.arrays)
    with _delta() as d, _delta("hostdata") as made, \
            _delta("hostdata.stage") as staged:
        _fit(table)
    assert _calls(d) == {"fit": 1, "hostdata.ingest": 1, "trainer.loop": 1,
                         "trainer.readback": 1}
    # the ingest span brackets a lookup: the table's facts (PR 46)
    assert made == {"placement_hits": 1, "label_facts_kept": 1} and staged == {}
    assert _self_sum(d) == pytest.approx(d["fit.seconds"], rel=1e-9)
    assert d["trainer.loop.self_seconds"] == d["trainer.loop.seconds"]
    del entry, table
    import gc
    gc.collect()
    assert metrics.group("hostdata").snapshot()["gauges"][
        "placement_kept_bytes"] == kept


def _ratings_table(seed=0, users=40, items=30, nnz=1500):
    rng = np.random.default_rng(seed)
    return Table({"user": rng.integers(0, users, nnz).astype(np.int32),
                  "item": rng.integers(0, items, nnz).astype(np.int32),
                  "rating": rng.uniform(0, 5, nnz).astype(np.float32)})


def test_an_als_fits_spans_are_siblings_in_order_and_a_second_fit_opens_fewer(tmp_path):
    """``ALS.fit(Table)`` (PR 38): ``als.ingest``, ``als.table_to_device``
    (holding the staging rounds), ``als.init``, ``als.loop`` (holding
    ``als.dispatch``) and ``als.readback`` one after another inside
    ``fit``; a second fit of the table ingests and places nothing, and
    the tree still adds up."""
    from flinkml_tpu.models import ALS

    fit = lambda t: ALS().set_rank(4).set_max_iter(2).fit(t)
    fit(_ratings_table(seed=1))  # compiled before the profile, on a table of its own
    table = _ratings_table()
    (fit_start, fit_end, name), *phases = _profiled_spans(tmp_path, lambda: fit(table))
    assert name == "fit"
    als = [ph for ph in phases if ph[2].startswith("als.")]
    assert [n for _, _, n in als] == ["als.ingest", "als.table_to_device", "als.init",
                                      "als.loop", "als.dispatch", "als.readback"]
    spans = {n: (a, b) for a, b, n in als}
    loop, dispatch = spans["als.loop"], spans["als.dispatch"]
    assert loop[0] <= dispatch[0] <= dispatch[1] <= loop[1]
    end = fit_start
    for start, stop, n in als:
        if n != "als.dispatch":
            assert end <= start <= stop <= fit_end, n
            end = stop
    # the staging rounds of both orders lie inside the upload
    up = spans["als.table_to_device"]
    rounds = [ph for ph in phases if not ph[2].startswith("als.")]
    assert rounds and all(up[0] <= a and b <= up[1] for a, b, _ in rounds)
    with _delta() as d:
        fit(table)
    assert set(_calls(d)) == {"fit", "als.init", "als.loop", "als.dispatch",
                              "als.readback"}
    assert _self_sum(d) == pytest.approx(d["fit.seconds"], rel=1e-9)


def _token_table(seed=0, words=300, tokens=20_000):
    from flinkml_tpu.table import TokenColumn

    rng = np.random.default_rng(seed)
    ends = np.cumsum(rng.integers(2, 30, tokens // 10))
    indptr = np.concatenate([[0], ends[ends < tokens], [tokens]])
    ids = (rng.random(tokens) ** 3 * words).astype(np.int32)
    return Table({"tok": TokenColumn(indptr, ids, np.arange(words).astype(str))})


def test_a_word2vec_fits_spans_are_siblings_in_order_and_a_second_fit_opens_fewer(tmp_path):
    """``Word2Vec.fit(Table)`` (PR 44): ``w2v.ingest``,
    ``w2v.table_to_device``, ``w2v.init``, ``w2v.loop`` (holding
    ``w2v.dispatch``) and ``w2v.readback`` one after another inside
    ``fit``; a second fit of the table ingests and places nothing, and the
    tree still adds up."""
    from flinkml_tpu.models import Word2Vec

    one = DeviceMesh(devices=jax.devices()[:1])
    fit = lambda t: (Word2Vec(mesh=one).set_input_col("tok").set_vector_size(8)
                     .set_min_count(1).set_batch_size(128).set_max_steps(3).fit(t))
    fit(_token_table(seed=1))  # compiled before the profile, on a table of its own
    table = _token_table()
    (fit_start, fit_end, name), *phases = _profiled_spans(tmp_path, lambda: fit(table))
    assert name == "fit"
    w2v = [ph for ph in phases if ph[2].startswith("w2v.")]
    assert [n for _, _, n in w2v] == ["w2v.ingest", "w2v.table_to_device", "w2v.init",
                                      "w2v.loop", "w2v.dispatch", "w2v.readback"]
    spans = {n: (a, b) for a, b, n in w2v}
    loop, dispatch = spans["w2v.loop"], spans["w2v.dispatch"]
    assert loop[0] <= dispatch[0] <= dispatch[1] <= loop[1]
    end = fit_start
    for start, stop, n in w2v:
        if n != "w2v.dispatch":
            assert end <= start <= stop <= fit_end, n
            end = stop
    with _delta() as d, _delta("w2v") as counted:
        fit(table)
    assert set(_calls(d)) == {"fit", "w2v.init", "w2v.loop", "w2v.dispatch",
                              "w2v.readback"}
    assert _self_sum(d) == pytest.approx(d["fit.seconds"], rel=1e-9)
    assert counted == {"fits": 1, "steps": 3, "pairs": 3 * 128,
                       "row_fetches": 3 * 128 * 7, "row_updates": 3 * 128 * 7,
                       "tokens": 20_000}


def _binary_table(seed=0, rows=1_500, features=5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, features)).astype(np.float32)
    return Table({"features": x, "label": (x[:, 0] * x[:, 1] > 0).astype(np.float32)})


def test_a_tree_fits_spans_are_siblings_in_order_and_a_second_fit_opens_fewer(tmp_path):
    """``GBTClassifier.fit(Table)`` on a dense column (PR 47):
    ``gbt.ingest``, ``gbt.table_to_device``, ``gbt.loop`` (holding
    ``gbt.dispatch``) and ``gbt.readback`` one after another inside
    ``fit``; a second fit of the table bins and places nothing, and the
    tree still adds up."""
    from flinkml_tpu.models import GBTClassifier

    one = DeviceMesh(devices=jax.devices()[:1])
    fit = lambda t: (GBTClassifier(mesh=one).set_num_trees(2).set_max_depth(3)
                     .set_max_bins(16).fit(t))
    fit(_binary_table(seed=1))  # compiled before the profile, on a table of its own
    table = _binary_table()
    (fit_start, fit_end, name), *phases = _profiled_spans(tmp_path, lambda: fit(table))
    assert name == "fit"
    gbt = [ph for ph in phases if ph[2].startswith("gbt.")]
    assert [n for _, _, n in gbt] == ["gbt.ingest", "gbt.table_to_device", "gbt.loop",
                                      "gbt.dispatch", "gbt.readback"]
    spans = {n: (a, b) for a, b, n in gbt}
    loop, dispatch = spans["gbt.loop"], spans["gbt.dispatch"]
    assert loop[0] <= dispatch[0] <= dispatch[1] <= loop[1]
    end = fit_start
    for start, stop, n in gbt:
        if n != "gbt.dispatch":
            assert end <= start <= stop <= fit_end, n
            end = stop
    with _delta() as d, _delta("gbt") as counted:
        fit(table)
    assert set(_calls(d)) == {"fit", "gbt.loop", "gbt.dispatch", "gbt.readback"}
    assert _self_sum(d) == pytest.approx(d["fit.seconds"], rel=1e-9)
    assert counted == {"fits": 1, "trees": 2, "levels": 6, "rows": 1_500,
                       "hist_cells": 6 * 1_536 * 5}     # product_levels: a CPU's 0


def _struct(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lowered_programs():
    """name -> a function that lowers that program of the hot path (the
    table "Programs" of docs/development/observability.md) at small
    shapes: its ``jax.stages.Lowered``."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flinkml_tpu.models import kmeans, knn
    from flinkml_tpu.ops import blas
    from flinkml_tpu.parallel import mesh as mesh_mod

    def m():
        return DeviceMesh().mesh

    def shardings():
        return NamedSharding(m(), P()), NamedSharding(m(), P("data"))

    f32, i32 = jnp.float32, jnp.int32

    def carry_and_tail(coef_shape):
        rep, _ = shardings()
        return ([_struct(coef_shape, f32, rep), _struct((), i32, rep),
                 _struct((), f32, rep)],
                [_struct((), f32, rep)] * 4 + [_struct((), i32, rep)])

    def dense(trainer, coef_shape):
        _, rows = shardings()
        head, tail = carry_and_tail(coef_shape)
        data = [_struct((64, 5), f32, rows), _struct((64,), f32, rows),
                _struct((64,), f32, rows)]
        return trainer.lower(*head, *data, *tail)

    def sparse():
        _, rows = shardings()
        head, tail = carry_and_tail((300,))
        data = [_struct((128, 7), i32, rows), _struct((128, 7), f32, rows),
                _struct((128,), f32, rows), _struct((128,), f32, rows)]
        trainer = _linear_sgd._sparse_trainer_bucketed(
            m(), "logistic", (8,), "data", 300)
        return trainer.lower(*head, *data, *tail)

    def stage_write():
        _, rows = shardings()
        tables = (_struct((64, 5), f32, rows), _struct((64,), f32, rows))
        blocks = (_struct((16, 5), f32, rows), _struct((16,), f32, rows))
        return mesh_mod._row_writer(m(), "data").lower(
            tables, blocks, _struct((), i32))

    def kmeans_lloyd():
        rep, rows = shardings()
        return kmeans._kmeans_trainer(m(), 3, "data").lower(
            _struct((64, 5), f32, rows), _struct((64,), f32, rows),
            _struct((64,), f32, rows), _struct((3, 5), f32, rep),
            _struct((), i32, rep))

    def fm_adam_loop():
        from flinkml_tpu.models import _fm_sparse

        rep, rows = shardings()
        return _fm_sparse._trainer(m(), True, 8, "data", (128, None)).lower(
            _struct((1,), f32, rep), _struct((4, 3, 128), f32, rep),
            _struct((128, 2), i32, rows), _struct((128, 2), f32, rows),
            _struct((128,), f32, rows), _struct((128,), f32, rows),
            _struct((2,), i32, rep), _struct((), f32, rep), _struct((), f32, rep),
            _struct((), i32, rep), _struct((), f32, rep))

    def fm_handover():
        from flinkml_tpu.models import _fm_sparse

        rep, _ = shardings()
        return _fm_sparse._handover.lower(_struct((4, 3, 128), f32, rep))

    def als_half_step():
        from flinkml_tpu.models import _als_blocked

        rep, rows = shardings()
        p = len(jax.devices())
        plan = _als_blocked.plan_side(np.array([3, 1, 40, 9] * p), p, 16)
        return _als_blocked._program(
            m(), plan.plan, 4, False, _als_blocked.GRAM_PRECISION, False).lower(
            _struct((p * plan.slots_local,), i32, rows),
            _struct((p * plan.slots_local,), f32, rows),
            _struct((p * plan.rows_local,), f32, rows),
            _struct((p * plan.owner.shape[1],), i32, rows),
            _struct((4 * p,), i32, rep), _struct((31, 128), f32, rep),
            _struct((), f32, rep), _struct((), f32, rep))

    def w2v_sgns_loop():
        from flinkml_tpu.models import _w2v_table

        u32, u16 = jnp.uint32, jnp.uint16
        d = _w2v_table.Draw(64, 2, 3, 128, 1000, 4096)
        return _w2v_table._program(d).lower(
            _struct((50, 128), f32), _struct((50, 128), f32),
            _struct((10, 128), i32), _struct((10, 128), u16), _struct((4096,), i32),
            _struct((), u32), _struct((), f32), _struct((), i32))

    def gbt_forest():
        from flinkml_tpu.models import _gbt_table

        rep, rows = shardings()
        p = len(jax.devices())
        across = jax.sharding.NamedSharding(m(), jax.sharding.PartitionSpec(None, "data"))
        return _gbt_table._program(
            m(), "data", 5, 16, 3, 2, True, True, 0, 3, (False,) * 3).lower(
            _struct((5, 128 * p), jnp.uint8, across), _struct((128 * p,), f32, rows),
            _struct((128 * p,), f32, rows), *[_struct((), f32, rep)] * 4,
            _struct((2,), jnp.uint32, rep))

    def mlp_programs():
        from flinkml_tpu.models import _mlp_table
        from flinkml_tpu.precision import MIXED

        rep, rows = shardings()
        layers = (5, 7, 3)
        params = tuple(_struct(s, f32, rep) for s in ((5, 7), (7,), (7, 3), (3,)))
        data = (_struct((64, 5), f32, rows), _struct((64,), i32, rows),
                _struct((64,), f32, rows))
        return {
            "mlp_fit": lambda: _mlp_table._trainer(
                m(), layers, True, 4, "data", 6, MIXED).lower(
                    params, *data, _struct((), f32, rep), _struct((), f32, rep)),
            "mlp_start": lambda: _mlp_table._start_program(layers).lower(
                jax.random.PRNGKey(0)),
        }

    def knn_vote():
        return knn._knn_vote.lower(
            _struct((16, 5), f32), _struct((64, 5), f32), _struct((64,), f32),
            _struct((64,), i32), k=3, num_classes=2, chunk=16, tile=64,
            precision=knn.PRODUCT_PRECISION)

    def fused_chain():
        seen = []
        real = pipeline_fusion._run_program

        def run(kernels, ext, outs, ext_specs, const_specs, ext_vals,
                const_vals, bucket, n, policy=None):
            seen.append((kernels, ext, outs, ext_vals, const_vals, bucket, n, policy))
            return real(kernels, ext, outs, ext_specs, const_specs, ext_vals,
                        const_vals, bucket, n, policy)

        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(pipeline_fusion, "_run_program", run)
            _score(_chain(), Table({"features": _lr_table(rows=64).column("features")}))
        kernels, ext, outs, ext_vals, const_vals, bucket, n, policy = seen[0]
        with jax.enable_x64(True):
            return jax.jit(pipeline_fusion._build_chain(
                kernels, ext, outs, bucket, policy)).lower(
                    tuple(ext_vals), const_vals, np.int32(n))

    return {
        "lr_dense_loop": lambda: dense(
            _linear_sgd._dense_trainer(m(), "logistic", 8, "data"), (5,)),
        "lr_sparse_loop": sparse,
        "lr_softmax_loop": lambda: dense(
            _linear_sgd._softmax_trainer(m(), 3, 8, "data"), (3, 5)),
        "stage_write": stage_write,
        "stage_zeros": lambda: mesh_mod._zero_rows(m(), "data").lower(
            (64, 5), np.dtype(np.float32)),
        "stage_ones": lambda: mesh_mod._ones_below(m(), "data").lower(
            _struct((), i32), 64, np.dtype(np.float32)),
        "kmeans_lloyd": kmeans_lloyd,
        "fm_adam_loop": fm_adam_loop,
        "fm_handover": fm_handover,
        "als_half_step": als_half_step,
        "w2v_sgns_loop": w2v_sgns_loop,
        "gbt_forest": gbt_forest,
        "knn_vote": knn_vote,
        "rows_sq": lambda: blas.squared_norms.lower(_struct((64, 5), f32)),
        "fused_chain": fused_chain,
        **mlp_programs(),
    }


PROGRAMS = ("lr_dense_loop", "lr_sparse_loop", "lr_softmax_loop", "stage_write",
            "stage_zeros", "stage_ones", "kmeans_lloyd", "fm_adam_loop", "fm_handover", "knn_vote",
            "rows_sq", "fused_chain", "als_half_step", "w2v_sgns_loop", "gbt_forest",
            "mlp_fit", "mlp_start")


@pytest.mark.parametrize("name", PROGRAMS)
def test_a_named_programs_module_carries_its_name(name):
    """What a profile's ``XLA Modules`` row calls each run of the
    program: ``jit_<name>``, the lowered module's own name."""
    first = _lowered_programs()[name]().as_text().splitlines()[0]
    # ``.<digits>``: the checksum of the phases the program declares
    # (``profiling.named_program``), which a profile's reader takes off.
    assert re.match(rf"module @jit_{name}(\.\d+)? ", first)


def test_the_docs_programs_table_lists_exactly_the_named_programs():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "development", "observability.md")) as f:
        doc = f.read()
    table = doc[doc.index("### Programs"):]
    table = table[:table.index("\n## ")] if "\n## " in table else table
    listed = set(re.findall(r"^\| `([a-z0-9_]+)` \|", table, flags=re.M))
    assert listed == set(PROGRAMS)
    named = set()
    for dirpath, _, files in os.walk(os.path.join(root, "flinkml_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name)) as f:
                    named |= set(re.findall(
                        r"named_program[,(]\s*\"([a-z0-9_]+)\"", f.read()))
    # _whole_loop's three callers hand their names down to its one call
    assert named | {"lr_dense_loop", "lr_sparse_loop", "lr_softmax_loop"} == set(PROGRAMS)
