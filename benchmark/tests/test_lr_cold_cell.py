"""What PR 37 added for ``lr-criteo.fit-cold``, on the CPU: the cell is
``lr-criteo.fit`` letter for letter but for its driver; every timed fit of
``fit_sparse_cold`` meets a table that keeps nothing (a miss, never a
hit), where every timed fit of ``fit`` and ``fit_sparse`` finds what
set-up's fit kept (a hit, set-up's the one miss); and ``BENCHMARK.json``
lists the cold cell wherever it lists ``lr-criteo.fit``."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
COLD, WARM = "lr-criteo.fit-cold", "lr-criteo.fit"
HIT_SHARE = "hostdata.placement_hit_share"


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = _read(os.path.dirname(BENCH), "BENCHMARK.json")


def _placements():
    from flinkml_tpu.utils.metrics import metrics

    counters = metrics.group("hostdata").snapshot()["counters"]
    return (counters.get("placement_hits", 0.0),
            counters.get("placement_misses", 0.0))


def _rehearse(cell, trace, capsys):
    """One rehearsal of ``cell``: its last line, and the hits and misses
    the program counted over set-up and window together."""
    from benchmark import run

    hits, misses = _placements()
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 37),
                   "--seconds", "0.3", "--trace", str(trace), "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    after = _placements()
    return line, after[0] - hits, after[1] - misses


def test_the_cold_cell_is_the_warm_cells_but_for_its_driver():
    cold = _read(BENCH, "workloads", f"{COLD}.json")
    warm = _read(BENCH, "workloads", f"{WARM}.json")
    told = {"driver", "why", "limits_from"}
    assert {k: v for k, v in cold.items() if k not in told} == {
        k: v for k, v in warm.items() if k not in told}
    assert (cold["driver"], warm["driver"]) == ("fit_sparse_cold", "fit_sparse")
    entries = {w["name"]: w for w in BENCHMARK["workloads"]}
    names = list(entries)
    assert names.index(COLD) > names.index(WARM)  # appended after it, not inserted
    assert entries[COLD] == {**entries[WARM], "name": COLD, "traffic": "fit-cold",
                             "why": cold["why"]}
    assert len(cold["why"]) <= 200 and "\n" not in cold["why"]


def test_the_cold_driver_is_fit_sparses_but_for_its_window():
    from benchmark.drivers import fit, fit_sparse, fit_sparse_cold

    assert fit_sparse_cold.setup is fit_sparse.setup
    assert fit_sparse_cold.check is fit_sparse.check
    assert fit_sparse_cold.dense._fit is fit._fit
    assert fit_sparse_cold.window is not fit.window


def test_the_benchmark_lists_the_cold_cell_wherever_it_lists_the_warm_one():
    listed = [m for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
              if "workloads" in m]
    with_warm = [m["name"] for m in listed if WARM in m["workloads"]]
    with_cold = [m["name"] for m in listed if COLD in m["workloads"]]
    # the cold cell alone feeds the host data path (PR 54), so it lists more
    assert set(with_warm) <= set(with_cold) and "fit_samples_per_s" in with_warm
    assert {"hostdata.shuffle_s_per_fit", "hostdata.sparse_pack_s_per_fit",
            "hostdata.upload_bytes_per_s"} <= set(with_cold) - set(with_warm)
    for m in listed:
        if WARM in m["workloads"]:  # appended after it: nothing else moved
            assert m["workloads"].index(COLD) > m["workloads"].index(WARM)
    (share,) = [m for m in BENCHMARK["per_layer"] if m["name"] == HIT_SHARE]
    assert share == {
        "name": HIT_SHARE, "unit": "fits/fit", "better": "higher",
        "source": "program_counter", "layer": "Host data",
        "moves": "fit_samples_per_s",
        "workloads": ["lr-a9a.fit", WARM, COLD]}
    reader = _read(BENCH, "metrics", f"{HIT_SHARE}.json")
    assert reader["reader"] == "counter_ratio"
    assert reader["params"] == {"num": "hostdata.placement_hits", "den": "fits"}


@pytest.mark.parametrize("trace", [0, 1])
def test_every_timed_fit_of_the_cold_cell_is_a_miss(trace, capsys):
    line, hits, misses = _rehearse(COLD, trace, capsys)
    assert hits == 0
    assert misses == line["attempted"] + 1  # set-up's fit and every timed one
    if trace:
        assert line["metrics"][HIT_SHARE]["value"] == 0.0
        # the miss is the fit as it was: it packs, permutes and sends
        assert line["metrics"]["hostdata.sparse_pack_s_per_fit"]["value"] > 0.0
        assert line["metrics"]["hostdata.shuffle_s_per_fit"]["value"] > 0.0
        assert line["metrics"]["hostdata.csr_materialized_rows"]["value"] == 0.0


@pytest.mark.parametrize("cell", [WARM, "lr-a9a.fit"])
def test_every_timed_fit_of_a_kept_cell_is_a_hit(cell, capsys):
    line, hits, misses = _rehearse(cell, 1, capsys)
    assert misses == 1  # set-up's fit
    assert hits == line["attempted"] >= 1
    assert line["metrics"][HIT_SHARE]["value"] == 1.0
    # a hit follows no placement, and the line has none of the host data path's
    assert not {"hostdata.shuffle_s_per_fit", "hostdata.upload_s_per_fit",
                "hostdata.stage_wait_s_per_fit", "hostdata.sparse_pack_s_per_fit",
                "hostdata.unit_weights_on_device_per_fit"} & set(line["metrics"])
    # what it keeps of the placement is set-up's: the window moved no count
    assert 0.0 < line["metrics"]["hostdata.staged_row_share"]["value"] <= 1.0


def test_the_share_is_absent_where_the_program_counts_no_placement():
    """The parent commit has no such counter: the reader returns nothing
    and the line leaves the metric out."""
    from benchmark.readers import counter_ratio

    params = _read(BENCH, "metrics", f"{HIT_SHARE}.json")["params"]
    assert counter_ratio.read(params, {"counters": {}, "units": {"fits": 3.0}}) is None
    assert counter_ratio.read(
        params, {"counters": {"hostdata.placement_hits": 0.0},
                 "units": {"fits": 3.0}}) == 0.0


def test_a_ratio_of_counters_the_window_did_not_move_is_set_ups():
    """A window of hits stages nothing: ``hostdata.staged_row_share`` is
    then the ratio of the placement set-up made. A count over the
    window's units, or a ratio whose window moved, never looks there."""
    from benchmark.readers import counter_ratio

    staged = _read(BENCH, "metrics", "hostdata.staged_row_share.json")["params"]
    setup = {"hostdata.stage.rows_sent": 500.0, "hostdata.stage.rows": 800.0,
             "hostdata.placement_hits": 2.0}
    still = {"hostdata.stage.rows_sent": 0.0, "hostdata.stage.rows": 0.0}
    for window in ({}, still):
        obs = {"counters": window, "setup_counters": setup, "units": {"fits": 3.0}}
        assert counter_ratio.read(staged, obs) == 0.625
    moved = {"hostdata.stage.rows_sent": 30.0, "hostdata.stage.rows": 40.0}
    assert counter_ratio.read(staged, {"counters": moved, "setup_counters": setup,
                                       "units": {"fits": 3.0}}) == 0.75
    assert counter_ratio.read(staged, {"counters": still, "setup_counters": {},
                                       "units": {}}) is None
    assert counter_ratio.read(staged, {"counters": still, "units": {}}) is None
    hits = _read(BENCH, "metrics", f"{HIT_SHARE}.json")["params"]
    assert counter_ratio.read(hits, {"counters": {}, "setup_counters": setup,
                                     "units": {"fits": 3.0}}) is None
