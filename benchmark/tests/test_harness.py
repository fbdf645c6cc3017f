"""The harness, driven on the CPU with ``--rehearse`` (which skips the
look for a chip and nothing else):

- a configuration, a cell and a per-layer metric added as FILES ONLY are
  found and run;
- with the timed path broken underneath, ``correct`` comes out false;
- without ``--rehearse`` and without a TPU the command exits non-zero
  and prints no result line.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _run_cli(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def _last_line(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.fixture()
def copy_of_benchmark(tmp_path):
    """A checkout-shaped directory holding BENCHMARK.json and benchmark/
    (the program is found through PYTHONPATH)."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    return tmp_path


def test_dummy_config_cell_and_metric_are_found_as_files_only(copy_of_benchmark):
    root = copy_of_benchmark
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    cfg = json.loads((root / "benchmark/configs/chain-a9a.json").read_text())
    cfg.update(name="dummy-chain", dim=7)
    (root / "benchmark/configs/dummy-chain.json").write_text(json.dumps(cfg))
    cell = json.loads((root / "benchmark/workloads/chain-a9a.transform.json").read_text())
    cell.update(config="dummy-chain", rehearse={"rows": 1000, "table_stride_rows": 8,
                                                "sample_rows": 100})
    (root / "benchmark/workloads/dummy-chain.scan.json").write_text(json.dumps(cell))
    (root / "benchmark/metrics/dummy.stages_per_call.json").write_text(json.dumps({
        "what": "fused stages a call", "reader": "counter_ratio",
        "params": {"num": "pipeline.fusion.fused_stages", "den": "calls"}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy-chain", "source": "test",
                             "file": "benchmark/configs/dummy-chain.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-chain.scan", "config": "dummy-chain",
                               "traffic": "scan", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("dummy-chain.scan")
    bench["per_layer"].append({
        "name": "dummy.stages_per_call", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Fused executor",
        "moves": "transform_rows_per_s", "workloads": ["dummy-chain.scan"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    plain = _run_cli(str(root), "--workload", "dummy-chain.scan", "--seed", "7",
                     "--seconds", "0.5", "--trace", "0", "--rehearse")
    assert plain.returncode == 0, plain.stderr[-2000:]
    line = _last_line(plain.stdout)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"transform_rows_per_s", "setup_s"}
    traced = _run_cli(str(root), "--workload", "dummy-chain.scan", "--seed", "7",
                      "--seconds", "0.5", "--trace", "1", "--rehearse")
    assert traced.returncode == 0, traced.stderr[-2000:]
    metrics = _last_line(traced.stdout)["metrics"]
    assert metrics["dummy.stages_per_call"] == {"value": 5.0, "unit": "count"}
    # a metric that lists other cells only is left out of this one's line
    assert "fusion.h2d_bytes_per_row" not in metrics
    # the 7-wide configuration was really the one run: 28 B a row
    assert "(28 B/row" in plain.stdout
    # nothing that was there was edited
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} changed"


def test_every_entry_of_benchmark_json_has_its_file():
    """BENCHMARK.json alone says what a metric is and where it is read; its
    file adds only how (``reader``, ``params``) and a line of ``what``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        with open(os.path.join(BENCH, "metrics", f"{m['name']}.json")) as f:
            own = json.load(f)
        assert set(own) <= {"what", "reader", "params"}, m["name"]
        assert os.path.exists(os.path.join(BENCH, "readers", f"{own['reader']}.py"))
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "workloads", f"{w['name']}.json")) as f:
            own = json.load(f)
        assert (own["config"], own["chips"], own["why"]) == (w["config"], w["chips"], w["why"])
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            own = json.load(f)
        assert (own["name"], own["source"], own["reduced"]) == (c["name"], c["source"], c["reduced"])


def test_without_a_tpu_the_command_refuses_and_prints_no_result():
    r = _run_cli(ROOT, "--workload", "chain-a9a.transform", "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "nothing was run" in r.stderr
    assert '"correct"' not in r.stdout


def test_in_a_directory_without_the_program_nothing_runs(copy_of_benchmark):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "chain-a9a.transform",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=str(copy_of_benchmark), env=env, capture_output=True, text=True,
        timeout=600)
    assert r.returncode != 0
    assert "not importable" in r.stderr and '"correct"' not in r.stdout


def test_an_unknown_workload_is_an_error():
    r = _run_cli(ROOT, "--workload", "no-such.cell", "--rehearse")
    assert r.returncode != 0 and '"correct"' not in r.stdout


def _main_in_process(capsys, *argv):
    from benchmark import run

    rc = run.main(list(argv))
    out = capsys.readouterr().out
    return rc, _last_line(out), out


def test_transform_with_an_answer_altered_where_it_is_produced(monkeypatch, capsys):
    from benchmark.drivers import transform

    real = transform._call

    def broken(state, x):
        out, pred = real(state, x)
        return out, 1.0 - pred              # every prediction flipped

    monkeypatch.setattr(transform, "_call", broken)
    rc, line, _ = _main_in_process(
        capsys, "--workload", "chain-a9a.transform", "--seed", "11",
        "--seconds", "0.3", "--trace", "0", "--rehearse")
    assert rc == 0 and line["correct"] is False


def test_transform_scoring_one_cached_table_fails_the_byte_count(monkeypatch, capsys):
    """Re-scoring one Table object skips the upload: the program's own
    byte counter then reads under 492 B/row and the run is not correct."""
    from benchmark.drivers import transform
    from flinkml_tpu.table import Table

    cache = {}

    def cached(state, x):
        key = x.__array_interface__["data"][0]
        table = cache.setdefault(key, Table({"features": x}))
        (out,) = state.model.transform(table)
        return out, np.asarray(out.column("prediction"))

    monkeypatch.setattr(transform, "_call", cached)
    rc, line, out = _main_in_process(
        capsys, "--workload", "chain-a9a.transform", "--seed", "12",
        "--seconds", "0.3", "--trace", "0", "--rehearse")
    assert rc == 0 and line["correct"] is False
    assert '"host-to-device bytes a row' in out


def test_fit_whose_step_returns_its_state_unchanged(monkeypatch, capsys):
    """A trainer that runs no step hands back zero coefficients: finite,
    equal from fit to fit, and caught by the gap to the replayed fit."""
    from benchmark.drivers import fit

    monkeypatch.setattr(
        fit, "_fit", lambda ctx, table, batch, max_iter: np.zeros(ctx.config["dim"]))
    rc, line, out = _main_in_process(
        capsys, "--workload", "lr-a9a.fit", "--seed", "13",
        "--seconds", "0.3", "--trace", "0", "--rehearse")
    assert rc == 0 and line["correct"] is False
    failed = [json.loads(l)["what"] for l in out.splitlines()
              if l.startswith('{"phase": "check"') and not json.loads(l)["ok"]]
    assert len(failed) == 1 and "coefficient gap to float64 SGD" in failed[0]


def test_fit_that_leaves_out_part_of_the_batch(monkeypatch, capsys):
    """A step over half the batch is a different update: the replay of
    the timed fit over whole batches catches it."""
    from benchmark.drivers import fit

    real = fit._fit

    def half(ctx, table, batch, max_iter):
        return real(ctx, table, max(1, batch // 2), max_iter)

    monkeypatch.setattr(fit, "_fit", half)
    rc, line, out = _main_in_process(
        capsys, "--workload", "lr-a9a.fit", "--seed", "14",
        "--seconds", "0.3", "--trace", "0", "--rehearse")
    assert rc == 0 and line["correct"] is False


def test_sound_rehearsals_are_correct(capsys):
    for cell in ("chain-a9a.transform", "lr-a9a.fit"):
        rc, line, _ = _main_in_process(
            capsys, "--workload", cell, "--seed", str(2 ** 31 + 5),
            "--seconds", "0.3", "--trace", "0", "--rehearse")
        assert rc == 0 and line["correct"] is True, cell
        assert line["device"]["platform"] == "cpu-rehearsal"
