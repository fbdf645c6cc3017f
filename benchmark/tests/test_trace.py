"""The trace reducer: on a trace made by hand, where every number can be
counted, and on two traces recorded on a v5e by the harness itself
(``--trace 1`` runs of PR 23: three transform calls; one fit, cut to its
first 20 steps' operations), where it must give what that run printed."""

import json
import os

import pytest

from benchmark import trace
from benchmark.readers import (counter_ratio, roofline, trace_busy_per_unit,
                               trace_idle_share)

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def _by_hand():
    """One chip, a 100 ms window, two 40 ms units. Unit 1: a 0.5 ms
    zero-fill at 2 ms, then at 20 ms a 10 ms loop whose two body
    operations (4 ms each) lie inside it. Unit 2: a 10 ms operation at
    70 ms. An operation outside the window is ignored."""
    ops = [["fill", 2 * MS, 0.5 * MS],
           ["while", 20 * MS, 10 * MS],
           ["body.a", 21 * MS, 4 * MS], ["body.b", 25 * MS, 4 * MS],
           ["big", 70 * MS, 10 * MS],
           ["late", 150 * MS, 5 * MS]]
    spans = [["bench:window", 0.0, 100 * MS],
             ["bench:unit", 0.0, 40 * MS], ["bench:unit", 50 * MS, 40 * MS],
             ["not-ours", 0.0, 100 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                            {"name": "XLA Modules", "events": [["m", 0, 99 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": spans}]},
        {"name": "#Chip0 Misc", "lines": []},
    ]}


def test_by_hand():
    r = trace.reduce(_by_hand())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["chips"] == 1
    # busy is a union: fill 0.5 + while 10 (its body is inside it) + big 10
    assert r["busy_mean_s"] == pytest.approx(0.0205)
    ops = dict(r["ops"])
    assert ops["while"] == pytest.approx(0.010) and ops["body.a"] == pytest.approx(0.004)
    assert "late" not in ops and "m" not in ops
    u1, u2 = r["spans"]
    assert (u1["name"], u2["name"]) == ("unit", "unit")
    assert u1["busy_s"] == pytest.approx(0.0105) and u2["busy_s"] == pytest.approx(0.010)
    # the lead skips the zero-fill (0.5 ms < a tenth of 10 ms)
    assert u1["lead_s"] == pytest.approx(0.020) and u2["lead_s"] == pytest.approx(0.020)
    gaps = dict(r["idle_gaps"])
    assert gaps["unit after window-start"] == pytest.approx(0.002)
    assert gaps["unit after fill"] == pytest.approx(0.0175)
    # 30..70 ms: its middle (50 ms) is where unit 2 starts
    assert gaps["unit after while"] == pytest.approx(0.040)
    assert gaps["unit after big"] == pytest.approx(0.020)
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.0205)


def test_two_chips_are_meaned_and_named():
    t = _by_hand()
    second = json.loads(json.dumps(t["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = [["big", 70 * MS, 10 * MS]]
    t["planes"].append(second)
    r = trace.reduce(t)
    assert r["chips"] == 2
    assert r["busy_s"] == {"/device:TPU:0": pytest.approx(0.0205),
                           "/device:TPU:1": pytest.approx(0.010)}
    assert r["busy_mean_s"] == pytest.approx(0.01525)
    assert r["spans"][1]["busy_s"] == pytest.approx(0.010)


def test_a_trace_without_the_window_span_or_a_chip_is_an_error():
    t = _by_hand()
    t["planes"][1]["lines"][0]["events"] = [["bench:unit", 0.0, 40 * MS]]
    with pytest.raises(ValueError, match="bench:window"):
        trace.reduce(t)
    t = _by_hand()
    del t["planes"][0]
    with pytest.raises(ValueError, match="device plane"):
        trace.reduce(t)


def test_clip_runs_and_names():
    ops = [("a", 0, 2), ("b", 1, 3), ("c", 3, 3), ("d", 5, 7), ("e", 6, 6.5)]
    assert trace._busy_runs(ops, 0, 10) == [[0, 3, "b"], [5, 7, "d"]]
    assert trace._busy_runs(ops, 2, 6) == [[2, 3, "b"], [5, 6, "d"]]
    assert trace.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert trace.op_name("%fusion.4 = (f32[]{:T(128)}) fusion(f32[9] %x)") == "fusion.4"


def _recorded(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def test_recorded_transform_trace_gives_what_the_run_printed():
    r = trace.reduce(_recorded("recorded_trace.json"))
    assert r["window_s"] == pytest.approx(0.757930543, rel=1e-9)
    assert r["busy_mean_s"] == pytest.approx(0.087192063, rel=1e-9)
    assert [s["name"] for s in r["spans"]] == ["transform-call"] * 3
    obs = {"trace": r, "traced_units": {"calls": 3, "rows": 3 * 4194304},
           "units": {}, "counters": {}, "setup_counters": {},
           "cell": {"rows": 4194304}, "config": {"dim": 123},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert trace_idle_share.read({}, obs) == pytest.approx(88.49603518353)
    per_call = trace_busy_per_unit.read({"span": "transform-call", "unit": "calls"}, obs)
    assert per_call == pytest.approx(29.064021)
    share = roofline.read({"span": "transform-call", "unit": "calls", "count": "chain",
                           "args": {"rows": "rows", "dim": "dim"}}, obs)
    # by hand: 4194304 * (123 * 4 + 12) B / 819e9 B/s = 2.5811 ms of 29.064 ms
    assert share == pytest.approx(100 * 4194304 * 504 / 819e9 / 0.029064021)
    assert share == pytest.approx(8.880774459412049)
    # the reducer's lead (its reader went with PR 54): four fifths of a call
    lead = sum(s["lead_s"] for s in r["spans"]) / sum(
        s["end_s"] - s["start_s"] for s in r["spans"])
    assert 0.79 < lead < 0.82
    top = r["idle_gaps"][0]
    assert top[0] == "transform-call after compare_convert_fusion"


def test_recorded_fit_trace_skips_the_zero_fill():
    r = trace.reduce(_recorded("recorded_trace_fit.json"))
    (fit,) = r["spans"]
    assert fit["name"] == "fit"
    assert r["busy_mean_s"] == pytest.approx(0.26757994, rel=1e-6)
    # the training loop starts 35.27 s into the 37.27 s fit; the first
    # device operation (a zero-fill) came at 29.3 s
    assert fit["lead_s"] == pytest.approx(35.2712, abs=1e-3)
    assert r["ops"][0][0] == "while.2"
    assert r["idle_gaps"][0] == ["fit after window-start", pytest.approx(29.325912633)]


def test_readers_return_nothing_when_there_is_nothing_to_read():
    obs = {"trace": None, "traced_units": {}, "units": {"rows": 10},
           "counters": {"g.bytes": 40.0}, "setup_counters": {"jax.cache_misses": 0.0},
           "cell": {}, "config": {}, "peaks": {}}
    assert trace_idle_share.read({}, obs) is None
    assert trace_busy_per_unit.read({"span": "fit", "unit": "steps"}, obs) is None
    assert counter_ratio.read({"num": "g.absent", "den": "rows"}, obs) is None
    assert counter_ratio.read({"num": "g.bytes", "den": "rows"}, obs) == 4.0
    assert counter_ratio.read({"num": "jax.cache_misses", "when": "setup"}, obs) == 0.0
