"""The readers of the span tree and of the chip's named programs
(``readers/_xplane_modules.py``, ``span_plain_seconds``,
``span_traced_seconds``, ``span_traced_excess``, ``trace_program_device_time``,
``roofline_of_program``, ``trace_idle_in_innermost_span``): on counters
and a two-chip trace made by hand, where every number can be counted;
every new metric file through ``run.load_spec``; and through a traced
rehearsal of each of six cells, which has the counters and no chip."""

import json
import os

import pytest

from benchmark import flops_bytes, run
from benchmark.readers import (_xplane_modules as xm, _xplane_program as xp,
                               roofline_of_program, span_plain_seconds,
                               span_traced_excess, span_traced_seconds,
                               trace_idle_in_innermost_span,
                               trace_idle_in_span, trace_program_device_time)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1e6  # ns

LR = ["lr-a9a.fit", "lr-criteo.fit"]
COLD = ["lr-criteo.fit-cold"]
FITS = LR + COLD + ["kmeans-mnist8m.fit"]
TRANSFORMS = ["chain-a9a.transform", "knn-mnist8m.transform"]
#: The cells whose traced run holds fits no profiler saw: not
#: ``lr-criteo.fit-cold``, whose one profiled fit can fill the window
#: (PERF.md §7).
DENSE, PLAIN_FITS = ["lr-a9a.fit"], ["lr-a9a.fit", "kmeans-mnist8m.fit"]
#: metric -> cells that list it, in the order PR 34 appended them. A
#: later issue may list a metric in further cells and append entries
#: after or between these; the host data path's metrics read in the cold
#: cell alone since PR 54 (every timed fit of the two kept cells finds
#: its placement kept, PR 37), and PR 54 retired the four the span tree
#: made for ``lr-a9a.fit``'s plain fits.
NEW = {
    "api.fit_own_s_per_fit": PLAIN_FITS,
    "trainer.loop_own_s_per_fit": DENSE,
    "tracing.traced_unit_excess_s.fit": PLAIN_FITS,
    "tracing.traced_unit_excess_s.transform": TRANSFORMS,
    "trainer.loop_device_ms_per_step": LR + COLD,
    "dense_lr_loop_roofline": DENSE,
    "sparse_lr_loop_roofline": ["lr-criteo.fit"] + COLD,
    "hostdata.stage_device_ms_per_fit": COLD,
    "device.idle_in_permute_s_per_fit": COLD,
    "device.idle_in_gather_s_per_fit": COLD,
    "device.idle_in_stage_wait_s_per_fit": COLD,
    "device.idle_in_loop_own_s_per_fit": LR + COLD,
    "api.fit_own_traced_s_per_fit": LR + COLD,
    "hostdata.permute_traced_s_per_fit": COLD,
    "hostdata.gather_traced_s_per_fit": COLD,
    "trainer.loop_own_traced_s_per_fit": LR + COLD,
}
#: Of them, read from the counters alone: a rehearsal prints them.
COUNTED = [m for m in NEW if m.split(".")[0] in ("api", "hostdata", "trainer", "tracing")
           and "device_ms" not in m]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_hand():
    """A 100 ms window, two chips, one traced fit.

    Chip 0's programs (``XLA Modules``): ``jit_stage_zeros(11)`` 2-4 ms,
    ``jit_stage_write(12)`` 30-33 and 60-63, ``jit_lr_dense_loop(13)``
    40-50 and 70-80, ``jit_convert_element_type(14)`` 90-91 (nobody's),
    and a loop that starts at 98 and runs 4 ms past the window's end.
    Chip 1 ran one loop, 70-80. Their ``XLA Ops`` rows hold the same
    intervals, so chip 0 idles 0-2, 4-30, 33-40, 50-60, 63-70, 80-90,
    91-98 = 69 ms and chip 1 0-70, 80-100 = 90 ms.

    The program's spans, one thread: ``fit`` 0-96 holds
    ``hostdata.shuffle`` 5-25 (which holds ``hostdata.permute`` 6-24)
    and ``trainer.loop`` 26-95, which holds two rounds of
    ``hostdata.stage_wait`` (28-29, 55-57), ``hostdata.shuffle`` (29-36,
    57-66) and ``mesh.shard_batch`` (36-38, 66-68)."""
    runs0 = [["jit_stage_zeros(11)", 2 * MS, 2 * MS],
             ["jit_stage_write(12)", 30 * MS, 3 * MS],
             ["jit_lr_dense_loop(13)", 40 * MS, 10 * MS],
             ["jit_stage_write(12)", 60 * MS, 3 * MS],
             ["jit_lr_dense_loop(13)", 70 * MS, 10 * MS],
             ["jit_convert_element_type(14)", 90 * MS, 1 * MS],
             ["jit_lr_dense_loop(13)", 98 * MS, 6 * MS]]
    runs1 = [["jit_lr_dense_loop(13)", 70 * MS, 10 * MS]]
    host = [["bench:window", 0.0, 100 * MS], ["bench:fit", 0.0, 97 * MS],
            ["flinkml:fit", 0.0, 96 * MS],
            ["flinkml:hostdata.shuffle", 5 * MS, 20 * MS],
            ["flinkml:hostdata.permute", 6 * MS, 18 * MS],
            ["flinkml:trainer.loop", 26 * MS, 69 * MS],
            ["flinkml:hostdata.stage_wait", 28 * MS, 1 * MS],
            ["flinkml:hostdata.shuffle", 29 * MS, 7 * MS],
            ["flinkml:mesh.shard_batch", 36 * MS, 2 * MS],
            ["flinkml:hostdata.stage_wait", 55 * MS, 2 * MS],
            ["flinkml:hostdata.shuffle", 57 * MS, 9 * MS],
            ["flinkml:mesh.shard_batch", 66 * MS, 2 * MS]]

    def chip(n, runs):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Ops", "events": [[f"op.{i}", s, d]
                                           for i, (_, s, d) in enumerate(runs)]},
            {"name": "XLA Modules", "events": runs}]}

    return {"planes": [chip(0, runs0), chip(1, runs1),
                       {"name": "/host:CPU", "lines": [
                           {"name": "python3", "events": host}]}]}


def _modules_form(t):
    """What ``_xplane_modules.load`` keeps of it: the ``XLA Modules``
    line, names normalised, and the ``bench:`` spans."""
    planes = []
    for plane in t["planes"]:
        if plane["name"] == "/host:CPU":
            lines = [{"name": ln["name"], "events": [
                e for e in ln["events"] if e[0].startswith("bench:")]}
                for ln in plane["lines"]]
        else:
            lines = [{"name": ln["name"], "events": [
                [xm.program_name(n), s, d] for n, s, d in ln["events"]]}
                for ln in plane["lines"] if ln["name"] == xm.MODULE_LINE]
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


@pytest.fixture()
def traced(monkeypatch):
    """Hands both sets of readers a trace as their ``this_run`` would."""
    def use(t):
        def pick(form):
            return lambda obs: form if obs.get("trace") else None

        monkeypatch.setattr(xp, "this_run", pick(t))
        monkeypatch.setattr(xm, "this_run", pick(_modules_form(t)))
        return {"trace": {"window_s": 0.1}, "units": {"fits": 4},
                "traced_units": {"fits": 1, "steps": 20}, "counters": {},
                "cell": {"global_batch_size": 262144}, "config": {"dim": 123},
                "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    return use


def test_program_names_lose_the_prefix_and_the_numbers():
    assert xm.program_name("jit_lr_sparse_loop(1234567890123)") == "lr_sparse_loop"
    assert xm.program_name("jit_lr_sparse_loop.2") == "lr_sparse_loop"
    assert xm.program_name("jit_stage_write.3(77)") == "stage_write"
    assert xm.program_name("jit__knn_vote(5)") == "_knn_vote"    # the parent's
    assert xm.program_name("broadcast_in_dim") == "broadcast_in_dim"


def test_program_time_sums_the_named_programs_inside_the_window(traced):
    obs = traced(_by_hand())

    def ms(programs, unit="steps"):
        return trace_program_device_time.read({"programs": programs, "unit": unit}, obs)

    # chip 0: 10 + 10 + the 2 ms of the last run inside the window; chip 1:
    # 10; meaned over the two chips, over 20 steps
    assert ms(["lr_dense_loop", "lr_sparse_loop"]) == pytest.approx((22 + 10) / 2 / 20)
    # the staging programs, a fit: chip 0 alone ran them (2 + 3 + 3), chip 1 none
    assert ms(["stage_write", "stage_zeros", "stage_ones"], "fits") == pytest.approx(8 / 2)
    assert ms(["stage_zeros"], "fits") == pytest.approx(2 / 2)
    assert ms(["kmeans_lloyd"]) is None          # no run of that name
    assert ms(["lr_dense_loop"], "rounds") is None   # no such unit traced


def test_a_profile_without_the_modules_line_or_a_rehearsal_reads_nothing(traced):
    t = _by_hand()
    for plane in t["planes"][:2]:
        plane["lines"] = plane["lines"][:1]      # XLA Ops only: an older trace
    obs = traced(t)
    params = {"programs": ["lr_dense_loop"], "unit": "steps"}
    assert trace_program_device_time.read(params, obs) is None
    assert roofline_of_program.read({**params, "module": "flops_bytes",
                                     "count": "dense_lr_step", "args": {}}, obs) is None
    rehearsal = {"trace": None, "traced_units": {"steps": 20}}
    assert xm.this_run(rehearsal) is None
    assert trace_program_device_time.read(params, rehearsal) is None


def test_roofline_of_program_is_the_counts_least_seconds_over_the_programs(traced):
    obs = traced(_by_hand())
    params = {"programs": ["lr_dense_loop"], "unit": "steps", "module": "flops_bytes",
              "count": "dense_lr_step", "args": {"batch": "global_batch_size", "dim": "dim"}}
    least, _ = flops_bytes.least_seconds(
        flops_bytes.dense_lr_step(batch=262144, dim=123), obs["peaks"])
    measured = (22 + 10) / 2 / 20 / 1e3
    assert roofline_of_program.read(params, obs) == pytest.approx(100 * least / measured)


def test_innermost_idle_adds_up_to_the_idle_time_under_the_root(traced):
    obs = traced(_by_hand())

    def innermost(name):
        return trace_idle_in_innermost_span.read({"span": name, "unit": "fits"}, obs)

    def inclusive(name):
        return trace_idle_in_span.read({"span": name, "unit": "fits"}, obs)

    # chip 0 / chip 1, ms. permute 6-24: 18 / 18.
    assert innermost("hostdata.permute") == pytest.approx((18 + 18) / 2 / 1e3)
    # shuffle less the permutation inside it: 5-6, 24-25, 29-36 (29-30, 33-36
    # idle on chip 0), 57-66 (57-60, 63-66): 2 + 4 + 6 / 2 + 7 + 9
    assert innermost("hostdata.shuffle") == pytest.approx((12 + 18) / 2 / 1e3)
    assert innermost("hostdata.stage_wait") == pytest.approx((1 + 2 + 1 + 2) / 2 / 1e3)
    # the loop less its rounds: 26-28, 38-55, 68-95; chip 0 idle 2 + (2 + 5)
    # + (2 + 10 + 4); chip 1 idle 2 + 17 + (2 + 15)
    assert innermost("trainer.loop") == pytest.approx((25 + 36) / 2 / 1e3)
    assert innermost("absent") is None
    names = ["fit", "hostdata.permute", "hostdata.shuffle", "hostdata.stage_wait",
             "mesh.shard_batch", "trainer.loop"]
    assert sum(innermost(n) for n in names) == pytest.approx(inclusive("fit"))
    # the inclusive reader counts the rounds' idle time under the loop too
    assert inclusive("trainer.loop") > innermost("trainer.loop")


def test_plain_seconds_leave_the_profiled_units_out():
    counters = {"span.fit.seconds": 10.0, "span.fit.self_seconds": 1.0,
                "span.fit.calls": 4.0, "span.fit.traced_seconds": 4.0,
                "span.fit.traced_self_seconds": 0.4, "span.fit.traced_calls": 1.0,
                "span.never_traced.seconds": 3.0, "span.never_traced.self_seconds": 3.0,
                "span.never_traced.calls": 3.0}
    obs = {"counters": counters, "units": {"fits": 4}, "traced_units": {"fits": 1}}

    def plain(span, field="seconds", den="fits"):
        return span_plain_seconds.read({"span": span, "field": field, "den": den}, obs)

    assert plain("fit") == pytest.approx((10.0 - 4.0) / 3)
    assert plain("fit", "self_seconds") == pytest.approx((1.0 - 0.4) / 3)
    assert plain("never_traced") == pytest.approx(3.0 / 3)   # ran in the plain fits only
    assert plain("absent") is None
    assert plain("fit", den="calls") is None                 # no such unit
    # every unit profiled: no plain unit to mean over
    assert span_plain_seconds.read(
        {"span": "fit", "field": "seconds", "den": "fits"},
        {**obs, "traced_units": {"fits": 4}}) is None
    assert span_traced_excess.read({"span": "fit"}, obs) == pytest.approx(4.0 - 2.0)
    # the other half: the profiled units' mean
    for field, want in (("seconds", 4.0), ("self_seconds", 0.4)):
        assert span_traced_seconds.read(
            {"span": "fit", "field": field, "den": "fits"}, obs) == pytest.approx(want)
    for span, den in (("never_traced", "fits"), ("absent", "fits"), ("fit", "calls")):
        assert span_traced_seconds.read(
            {"span": span, "field": "seconds", "den": den}, obs) is None
    # one profiled unit fills the window (lr-criteo.fit): no plain mean,
    # no excess, and the split is the profiled fit's
    alone = {**obs, "units": {"fits": 1}}
    assert plain("fit") is not None and span_plain_seconds.read(
        {"span": "fit", "field": "seconds", "den": "fits"}, alone) is None
    assert span_traced_seconds.read(
        {"span": "fit", "field": "seconds", "den": "fits"}, alone) == pytest.approx(4.0)
    assert span_traced_excess.read({"span": "never_traced"}, obs) is None
    assert span_traced_excess.read({"span": "absent"}, obs) is None
    # a plain run: nothing traced, the whole window is plain
    obs = {"counters": {k: v for k, v in counters.items() if "traced" not in k},
           "units": {"fits": 4}, "traced_units": {}}
    assert plain("fit") == pytest.approx(10.0 / 4)
    assert span_traced_excess.read({"span": "fit"}, obs) is None


def test_a_parent_without_the_span_tree_reads_nothing(traced):
    """The driver lays these files over the parent's checkout: its spans
    count ``seconds`` and ``calls`` alone, its programs are all
    ``jit_per_device``, and no reader may raise."""
    parent = {"counters": {"span.fit.seconds": 10.0, "span.fit.calls": 4.0},
              "units": {"fits": 4}, "traced_units": {"fits": 1}}
    for field in ("seconds", "self_seconds"):
        assert span_plain_seconds.read(
            {"span": "fit", "field": field, "den": "fits"}, parent) is None
    assert span_traced_excess.read({"span": "fit"}, parent) is None
    assert span_traced_seconds.read(
        {"span": "fit", "field": "seconds", "den": "fits"}, parent) is None
    t = _by_hand()
    for plane in t["planes"][:2]:
        for event in plane["lines"][1]["events"]:
            event[0] = "jit_per_device(9)"
    obs = traced(t)
    assert trace_program_device_time.read(
        {"programs": ["lr_dense_loop", "lr_sparse_loop"], "unit": "steps"}, obs) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_new_metric_loads_for_exactly_the_cells_it_lists(metric):
    bench = _bench()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert set(NEW[metric]) <= set(entry["workloads"])
    for cell in (w["name"] for w in bench["workloads"]):
        loaded = [m for m in run.load_spec(ROOT, cell)["per_layer"]
                  if m["name"] == metric]
        assert len(loaded) == (cell in entry["workloads"])
        for m in loaded:
            assert m["reader"] and m["what"] and m["moves"] in {
                e["name"] for e in run.load_spec(ROOT, cell)["end_to_end"]}


def test_the_new_entries_are_appended_and_keep_to_the_form():
    """Appended, never inserted: among themselves in the order they
    came, after every layer they name was there. Later issues append
    after them (and a ``benchmark`` issue may retire one), so no position
    is counted from the end."""
    bench = _bench()
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in NEW]
    assert at == sorted(at)
    layers = {m["layer"] for m in bench["per_layer"][:at[0]]}
    for m in (bench["per_layer"][i] for i in at):
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert len(m["name"]) <= 64 and m["layer"] in layers
        assert m["source"] in ("program_span", "device_trace")
    assert len(bench["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


@pytest.mark.parametrize("cell", FITS + TRANSFORMS)
def test_a_traced_rehearsal_of_every_cell_still_prints_its_line(cell, capsys):
    """The metrics read from the counters are there in the cells that
    list them (a rehearsal profiles its first units as a chip run does),
    those read from the chip's profile are absent, and none raises."""
    assert run.main(["--workload", cell, "--seed", "2147493104", "--seconds", "1",
                     "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    listed = {m["name"] for m in run.load_spec(ROOT, cell)["per_layer"]}
    counted = set(COUNTED) & listed
    assert {m for m in COUNTED if cell in NEW[m]} <= counted
    assert set(line["metrics"]) & set(NEW) == counted
    value = {m: line["metrics"][m]["value"] for m in counted}
    assert all(v >= 0 for m, v in value.items() if "tracing" not in m)
    if cell in PLAIN_FITS:
        assert value["api.fit_own_s_per_fit"] > 0
    if cell in COLD:
        # the profiled fit's permutation and gathers are its hostdata.shuffle
        assert (value["hostdata.permute_traced_s_per_fit"]
                + value["hostdata.gather_traced_s_per_fit"]) > 0
    if cell in LR:
        # a hit: the loop is the fit, and nothing the host data path reads is there
        assert 0 < value["trainer.loop_own_traced_s_per_fit"]
        assert not {m for m in line["metrics"] if m.startswith("hostdata.permute")}
