"""The readers of the program's own spans
(``readers/_xplane_program.py`` and the three readers on it): on a trace
made by hand, where every number can be counted; on a trace recorded on
a v5e by the harness (``--trace 1`` of ``chain-a9a.transform``, PR 24,
in ``_xplane_program.load``'s plain-data form), where they must give
what that run printed; and through a rehearsal of three cells, which has
the counters and no chip."""

import importlib
import json
import os

import pytest

from benchmark.readers import (_xplane_program as xp, span_self_seconds,
                               trace_idle_in_span, trace_idle_outside_spans)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MS = 1e6  # ns
ROOTS = {"roots": ["fit", "transform"]}


def _by_hand(chips=1):
    """A 100 ms window. Chip 0 runs an operation at 5-10 ms, a ``while``
    at 40-50 ms whose two body operations lie inside it, and one at
    70-80 ms: idle 0-5, 10-40, 50-70, 80-100 = 75 ms. The program's
    spans: the root ``fit`` 0-95; ``a`` 10-30 (the gap 10-40 straddles
    its end); ``b`` 50-60; ``c`` 82-90 (wholly inside a gap); ``d`` 41-44
    (wholly inside busy time on chip 0). Chip 1 runs only the last
    operation: idle 0-70 and 80-100."""
    ops = [["dot.1", 5 * MS, 5 * MS], ["while.2", 40 * MS, 10 * MS],
           ["fusion.3", 41 * MS, 4 * MS], ["fusion.4", 45 * MS, 4 * MS],
           ["fusion.5", 70 * MS, 10 * MS], ["fusion.6", 150 * MS, 5 * MS]]
    host = [["bench:window", 0.0, 100 * MS], ["bench:fit", 0.0, 96 * MS],
            ["flinkml:fit", 0.0, 95 * MS], ["flinkml:a", 10 * MS, 20 * MS],
            ["flinkml:b", 50 * MS, 10 * MS], ["flinkml:c", 82 * MS, 8 * MS]]
    other_thread = [["flinkml:d", 41 * MS, 3 * MS]]

    def chip(n, rows):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Ops", "events": rows}]}

    planes = [chip(0, ops)] + [chip(1, ops[4:5])] * (chips - 1)
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": host},
        {"name": "worker", "events": other_thread}]})
    return {"planes": planes}


@pytest.fixture()
def traced(monkeypatch):
    """Hands the readers a trace as ``this_run`` would."""
    def use(t):
        monkeypatch.setattr(xp, "this_run", lambda obs: t if obs.get("trace") else None)
        return {"trace": {"window_s": 0.1}, "traced_units": {"fits": 1, "steps": 3, "calls": 2},
                "units": {}, "counters": {}}
    return use


def test_idle_outside_spans_by_hand(traced):
    obs = traced(_by_hand())
    # covered idle: a 20 (of the gap 10-40), b 10, c 8, d 0 (the chip is busy) = 38 of 75
    assert trace_idle_outside_spans.read(ROOTS, obs) == pytest.approx(100 * 37 / 75)
    # with the root as cover, only 95-100 ms is outside
    assert trace_idle_outside_spans.read({}, obs) == pytest.approx(100 * 5 / 75)


def test_idle_in_span_inside_outside_and_straddling(traced):
    obs = traced(_by_hand())

    def idle(name, unit="fits"):
        return trace_idle_in_span.read({"span": name, "unit": unit}, obs)

    assert idle("c") == pytest.approx(0.008)          # wholly inside a gap
    assert idle("d") == pytest.approx(0.0)            # wholly inside busy time
    assert idle("a") == pytest.approx(0.020)          # the gap runs on past its end
    assert idle("b", "calls") == pytest.approx(0.010 / 2)
    assert idle("absent") is None
    assert idle("a", "rows") is None                  # no such unit traced


def test_two_chips_are_meaned(traced):
    obs = traced(_by_hand(chips=2))
    # chip 1: idle 90; covered a 20, b 10, c 8, d 3 = 41
    assert trace_idle_outside_spans.read(ROOTS, obs) == pytest.approx(
        100 * (37 / 75 + 49 / 90) / 2)
    assert trace_idle_in_span.read({"span": "d", "unit": "fits"}, obs) == pytest.approx(0.0015)


def test_a_while_and_its_body_are_busy_once(traced):
    """The gap 10-40 ends where the ``while`` starts and 50-70 begins
    where it ends, whatever its body operations add inside it."""
    t = _by_hand()
    assert xp.idle_by_chip(t, 0.0, 100 * MS)["/device:TPU:0"] == [
        (0.0, 5 * MS), (10 * MS, 40 * MS), (50 * MS, 70 * MS), (80 * MS, 100 * MS)]
    t["planes"][-1]["lines"][0]["events"].append(["flinkml:loop", 35 * MS, 20 * MS])
    obs = traced(t)
    # 35-55 holds the while (10 busy) and idle 35-40 and 50-55
    assert trace_idle_in_span.read({"span": "loop", "unit": "fits"}, obs) == pytest.approx(0.010)


def test_recorded_transform_trace_gives_what_the_run_printed(traced):
    """Three traced calls of ``chain-a9a.transform`` on a v5e (PR 24):
    the upload returns in 0.4 ms and the host waits in ``table.to_host``."""
    with open(os.path.join(HERE, "recorded_program_trace.json")) as f:
        t = json.load(f)
    obs = traced(t)
    obs["traced_units"] = {"calls": 3}
    assert [s[0] for s in xp.program_spans(t)] == [
        "transform", "table.to_device", "fusion.constants", "fusion.dispatch",
        "table.to_host"] * 3
    assert trace_idle_outside_spans.read(ROOTS, obs) == pytest.approx(0.38864931378755907)

    def idle(name):
        return trace_idle_in_span.read({"span": name, "unit": "calls"}, obs)

    assert idle("table.to_device") == pytest.approx(0.00039556666666666667)
    assert idle("fusion.constants") == pytest.approx(0.035603260000000005)
    assert idle("table.to_host") == pytest.approx(0.1807792433333333)
    # the old reducer reads the same file as before
    from benchmark import trace
    assert trace.reduce(t)["busy_mean_s"] == pytest.approx(0.087192356, rel=1e-9)


def test_span_self_seconds_is_counters_only():
    obs = {"counters": {"span.fit.seconds": 70.0, "span.a.seconds": 40.0,
                        "span.b.seconds": 24.0, "span.other.seconds": 5.0},
           "units": {"fits": 2}}
    params = {"span": "fit", "children": ["a", "b", "never-ran"], "den": "fits"}
    assert span_self_seconds.read(params, obs) == pytest.approx(3.0)
    assert span_self_seconds.read({**params, "span": "absent"}, obs) is None
    assert span_self_seconds.read({**params, "den": "calls"}, obs) is None


def test_a_parent_without_spans_reads_nothing(traced):
    """The driver lays these files over the parent's checkout: its trace
    has no ``flinkml:`` span, and no reader may raise."""
    t = _by_hand()
    t["planes"][-1]["lines"] = [{"name": "python3", "events": [
        ["bench:window", 0.0, 100 * MS], ["bench:fit", 0.0, 96 * MS]]}]
    obs = traced(t)
    assert trace_idle_outside_spans.read(ROOTS, obs) is None
    assert trace_idle_in_span.read({"span": "a", "unit": "fits"}, obs) is None
    obs = {"counters": {"pipeline.fusion.compiles": 0.0}, "units": {"fits": 1}}
    assert span_self_seconds.read({"span": "fit", "children": [], "den": "fits"}, obs) is None


def test_a_rehearsal_reads_nothing_and_a_traced_run_without_its_one_file_fails(
        tmp_path, monkeypatch):
    rehearsal = {"trace": None, "traced_units": {"fits": 1}}
    assert xp.this_run(rehearsal) is None
    assert trace_idle_outside_spans.read(ROOTS, rehearsal) is None
    assert trace_idle_in_span.read({"span": "x", "unit": "fits"}, rehearsal) is None
    # A run the harness reduced a trace for has written one profile. None
    # by this process, or two, is a fault of the finder: loud, not None.
    traced_run = {"trace": {"window_s": 1.0}, "traced_units": {"fits": 1}}
    monkeypatch.setattr(xp, "OUT_TRACE", str(tmp_path))
    monkeypatch.setattr(xp, "load", lambda path: {"planes": [], "path": path})
    xp._this_runs_file.cache_clear()
    try:
        old = tmp_path / "cell" / "plugins" / "profile" / "t0"
        old.mkdir(parents=True)
        (old / "vm.xplane.pb").write_bytes(b"")
        os.utime(old / "vm.xplane.pb", (1.0, 1.0))      # from an earlier run
        with pytest.raises(RuntimeError, match="found \\[\\]"):
            trace_idle_outside_spans.read(ROOTS, traced_run)
        new = tmp_path / "cell" / "plugins" / "profile" / "t1"
        new.mkdir()
        (new / "vm.xplane.pb").write_bytes(b"")
        assert xp.this_run(traced_run)["path"] == str(new / "vm.xplane.pb")
        xp._this_runs_file.cache_clear()
        other = tmp_path / "other-cell" / "plugins" / "profile" / "t1"
        other.mkdir(parents=True)
        (other / "vm.xplane.pb").write_bytes(b"")
        with pytest.raises(RuntimeError, match="other-cell"):
            trace_idle_in_span.read({"span": "x", "unit": "fits"}, traced_run)
    finally:
        xp._this_runs_file.cache_clear()


def test_helpers():
    assert xp.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xp.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    t = _by_hand()
    assert xp.window(t) == (0.0, 100 * MS)
    assert [s[0] for s in xp.program_spans(t)] == ["fit", "a", "d", "b", "c"]


def test_every_metric_file_names_a_reader_that_exists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    files = sorted(os.listdir(os.path.join(BENCH, "metrics")))
    assert {f[:-len(".json")] for f in files} == set(listed)
    for name in files:
        with open(os.path.join(BENCH, "metrics", name)) as f:
            own = json.load(f)
        reader = importlib.import_module(f"benchmark.readers.{own['reader']}")
        assert callable(reader.read), name
        assert isinstance(own.get("params", {}), dict) and own["what"]


#: The metrics PR 24 read off the spans' counters, in the cells that list
#: them now: the host data path's in the cold cell alone (PR 54; every
#: timed fit of ``lr-a9a.fit`` finds its placement kept since PR 37).
COUNTED = {
    "lr-a9a.fit": ["hostdata.ingest_s_per_fit", "trainer.loop_own_s_per_fit",
                   "trainer.readback_s_per_fit", "api.fit_own_s_per_fit"],
    "lr-criteo.fit-cold": ["hostdata.ingest_s_per_fit", "hostdata.shuffle_s_per_fit",
                           "hostdata.upload_s_per_fit", "hostdata.upload_bytes_per_s",
                           "hostdata.stage_wait_s_per_fit",
                           "hostdata.sparse_pack_s_per_fit",
                           "trainer.readback_s_per_fit"],
    "chain-a9a.transform": ["fusion.upload_s_per_call", "fusion.constants_s_per_call",
                            "fusion.dispatch_s_per_call", "fusion.readback_s_per_call",
                            "api.transform_self_s_per_call"],
}
TRACED = ["device.idle_outside_spans.fit", "device.idle_outside_spans.transform",
          "device.idle_in_loop_own_s_per_fit", "device.idle_in_upload_s_per_call"]


@pytest.mark.parametrize("cell", sorted(COUNTED))
def test_a_traced_rehearsal_prints_the_counted_metrics_only(cell, capsys):
    from benchmark import run

    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 24),
                   "--seconds", "0.3", "--trace", "1", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    metrics = line["metrics"]
    for name in COUNTED[cell]:
        assert metrics[name]["value"] >= 0.0, name
    assert not set(TRACED) & set(metrics)
    if cell == "lr-a9a.fit":
        # the phases and the fit's own time are the fit: they add up to a fit's wall
        phases = sum(metrics[n]["value"] for n in COUNTED[cell] if n.endswith("_s_per_fit"))
        assert phases > 0 and metrics["api.fit_own_s_per_fit"]["value"] < phases
