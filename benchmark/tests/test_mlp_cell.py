"""What PR 52 added for ``mlp-mnist8m.fit``, on the CPU: the reference's
written-out backward pass against ``jax.grad`` and its windows against
their arithmetic, the step's count against its own, the configuration and
the entries' form, and a rehearsal of the cell, traced and not, and of the
builder's control script, whose planted faults (``drivers.mlp.FAULTS``:
operands cut to four bits, half of every window left out of the loop's
step, an array never updated) fail the cell's own checks here too: they
are written out, so a CPU shows them. The metric sets are held as SUBSETS:
the next metric a cell gains must not break them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_bytes, flops_bytes_mlp
from benchmark.reference import mlp as reference

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL_NAME = "mlp-mnist8m.fit"
LAYERS = [784, 2500, 2000, 1500, 1000, 500, 10]

with open(os.path.join(BENCH, "configs", "mlp-mnist8m.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", f"{CELL_NAME}.json")) as f:
    CELL = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

#: The cell's per-layer metrics: five of its own (``per_layer`` holds 128 at
#: most and had 123: the uploads and the policy's steps are ``check``'s,
#: the host's share of a fit the idle shares') and five that were there.
COUNTED = ["compile.cache_misses.setup", "hostdata.label_facts_kept_share"]
TRACED = ["mlp.step_device_ms", "mlp.forward_device_ms", "mlp.backward_device_ms",
          "mlp.adam_device_ms", "mlp_step_mfu",
          "device.idle_share.fit", "device.idle_outside_spans.fit"]
#: The checks held to a limit of the cell's file; the first four read what
#: the LAST TIMED fit returned.
LIMITED = ("first_loss_gap", "loss_curve_gap", "param_change_gap", "trained",
           "start_loss_gap", "grad_gap")
SPANS = ["api.fit_own_traced_s_per_fit"]


def _net(layers, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(a for d_in, d_out in zip(layers, layers[1:]) for a in (
        (rng.normal(size=(d_in, d_out)) * np.sqrt(2.0 / d_in)).astype(np.float32),
        (0.1 * rng.normal(size=d_out)).astype(np.float32)))


def test_the_written_out_backward_pass_is_autodiffs_and_blocks_add_up():
    import jax
    import jax.numpy as jnp

    layers = [12, 9, 7, 4]
    params = _net(layers)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(50, 12)).astype(np.float32)
    y = rng.integers(0, 4, 50)
    c = rng.uniform(0.5, 2.0, 50).astype(np.float32)

    def loss(params):
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(reference.forward(params, jnp.asarray(x))[1])
        return -jnp.sum(logp[jnp.arange(50), y] * c) / c.sum()

    want_loss, want = jax.value_and_grad(loss)(tuple(jnp.asarray(p) for p in params))
    got_loss, got = reference.loss_and_gradients(params, x, y, c)
    assert abs(float(got_loss) - float(want_loss)) < 1e-6
    assert max(reference.relative_gaps(got, want)) < 1e-5
    blocks = reference.loss_and_gradients(params, x, y, c, block=16)
    assert abs(float(blocks[0]) - float(got_loss)) < 1e-6
    assert max(reference.relative_gaps(blocks[1], got)) < 1e-5
    rows = np.asarray(reference.row_losses(params, x, y.astype(np.int32)))
    assert abs(float((rows * c).sum() / c.sum()) - float(got_loss)) < 1e-6


@pytest.mark.parametrize("rows,batch", [(1000, 256), (1024, 256), (100, 256)])
def test_the_windows_cover_the_order_and_the_last_is_pulled_back(rows, batch):
    order = reference.seeded_order(7, rows)
    assert sorted(order.tolist()) == list(range(rows))
    held = min(batch, rows)
    windows = -(-rows // held)
    seen = np.concatenate([reference.step_rows(order, batch, t) for t in range(windows)])
    assert set(seen.tolist()) == set(range(rows))
    for t in range(windows + 1):
        got = reference.step_rows(order, batch, t)
        start = min((t % windows) * held, rows - held)
        assert np.array_equal(got, order[start:start + held])


def test_adam_is_the_published_rule_and_a_fit_learns():
    layers = [6, 8, 3]
    rng = np.random.default_rng(2)
    x = rng.normal(size=(256, 6)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64) + (x[:, 1] > 0)
    params0 = _net(layers)
    order = reference.seeded_order(3, 256)
    params, losses = reference.fit(x, y, order, params0, 0.01, 60, 64)
    assert losses.shape == (60,) and losses[-1] < 0.5 * losses[0]
    # one step by hand, float64
    rows = reference.step_rows(order, 64, 0)
    _, grads = reference.loss_and_gradients(params0, x[rows], y[rows])
    one, _ = reference.fit(x, y, order, params0, 0.01, 1, 64)
    for p0, g, p1 in zip(params0, grads, one):
        g = np.asarray(g, np.float64)
        m, v = 0.1 * g, 0.001 * g * g
        want = p0 - 0.01 * (m / 0.1) / (np.sqrt(v / 0.001) + 1e-8)
        np.testing.assert_allclose(p1, want, atol=2e-6)


def test_the_steps_count_is_its_own_arithmetic():
    count = flops_bytes_mlp.step(16384, LAYERS)
    weights = sum(a * b for a, b in zip(LAYERS, LAYERS[1:]))
    assert weights == CONFIG["weights"] == 11_965_000
    assert sum(LAYERS[1:]) == CONFIG["biases"] == 7_510
    assert count["flops"] == 6 * 16384 * weights - 2 * 16384 * 784 * 2500
    assert count["bytes"] == 16384 * 786 * 4 + 6 * (weights + 7_510) * 4
    least, bound = flops_bytes.least_seconds(
        count, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "flops" and 5.6e-3 < least < 5.7e-3


def test_the_configuration_and_the_entries():
    assert CONFIG["architecture"] is None
    assert CONFIG["reduced"] == ["train_rows", "epochs"]
    assert CONFIG["layers"] == LAYERS and CONFIG["precision"] == "mixed"
    assert (CONFIG["train_rows"], CONFIG["train_rows_source"]) == (2_025_000, 8_100_000)
    assert (CONFIG["global_batch_size"], CONFIG["max_iter"], CONFIG["tol"]) == (16384, 124, 0.0)
    assert CONFIG["max_iter"] == -(-CONFIG["train_rows"] // CONFIG["global_batch_size"])
    assert len(CONFIG["source"]) <= 200 and len(CONFIG["guarantees"]) == 5
    assert {"optimizer", "learning_rate", "global_batch_size", "start",
            "hidden_activation", "profile", "train_rows", "epochs"} <= set(CONFIG["assumed"])
    (entry,) = [c for c in BENCHMARK["configs"] if c["name"] == "mlp-mnist8m"]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "benchmark/configs/mlp-mnist8m.json"
    (cell,) = [w for w in BENCHMARK["workloads"] if w["name"] == CELL_NAME]
    assert cell["chips"] == CELL["chips"] == 1 and cell["why"] == CELL["why"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert CELL["sweep"] == [0.001, 0.0003] and CELL["driver"] == "mlp"
    assert set(CELL["limits"]) == set(LIMITED)
    # a rehearsal overrides the rows, the batch and the steps (and the limits
    # read at them), never a width
    assert set(CELL["rehearse"]) == {"train_rows", "global_batch_size", "max_iter",
                                     "limits"}
    assert set(CELL["rehearse"]["limits"]) == set(LIMITED)
    mine = {m["name"] for m in BENCHMARK["per_layer"] if CELL_NAME in m.get("workloads", [])}
    assert set(COUNTED + TRACED + SPANS) == mine
    assert len(BENCHMARK["per_layer"]) <= 128
    assert all(m["layer"] == "Trainers" for m in BENCHMARK["per_layer"]
               if m["name"].startswith("mlp"))
    rate = next(m for m in BENCHMARK["end_to_end"] if m["name"] == "fit_samples_per_s")
    assert CELL_NAME in rate["workloads"]      # not "the last": the next cell's goes after
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{name}.json")), name
    # device.unphased_share.fit's file names its programs, mlp_fit not among
    # them: the cell is not on its list (the step less its three phases is
    # that time)
    unphased = next(m for m in BENCHMARK["per_layer"]
                    if m["name"] == "device.unphased_share.fit")
    assert CELL_NAME not in unphased["workloads"]


def _run(*extra):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL_NAME,
         "--seed", "2147493105", "--seconds", "1", "--rehearse", *extra],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    return lines[-1], lines


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_cell(trace):
    line, lines = _run("--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    # an odd seed: a second's window holds one fit, the sweep's lowest rate,
    # which the rehearsal's limits are read at
    checks = [c for c in lines if c.get("phase") == "check"]
    assert len(checks) == 13 and all(c["ok"] for c in checks)
    assert "start_gap" in checks[1]["what"] and checks[1]["limit"] == 1e-6
    for name, check in zip(LIMITED, checks[2:]):
        assert name in check["what"]
        assert 0 < check["value"] < check["limit"] == CELL["rehearse"]["limits"][name]
        assert ("last timed fit at the lowest rate (0.0003" in check["what"]) == (
            name in LIMITED[:4])
    assert [c["value"] for c in checks[8:]] == [0, 0.0, 0.0, 0.0, 0]
    if trace:
        assert set(COUNTED + SPANS) <= set(line["metrics"])
        assert line["metrics"]["hostdata.label_facts_kept_share"]["value"] == 1.0
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}


def test_the_builders_control_script_rehearses_and_every_planted_fault_fails():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "chip_controls_mlp.py"), "--seeds", "1",
         "--rehearse"], capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    rates = [ln for ln in lines if "sound_failed" in ln]
    assert [ln["rate"] for ln in rates] == CELL["sweep"]
    timed = set(LIMITED[:4])
    for line in rates:
        assert line["float32_failed"] == []
        assert line["float32_grad_gap"] < 1e-5 and line["float32_param_change_gap"] < 1e-3
        # one precision lower: the function outside the loop shows it
        assert {"start_loss_gap", "grad_gap"} <= set(line["four_bits_failed"])
        assert set(line["half_window_failed"]) <= timed
        assert "param_change_gap" in line["frozen_leaf_failed"]
        assert line["frozen_leaf_param_change_gap"] == 1.0
        assert {"first_loss_gap", "param_change_gap"} <= set(line["other_start_failed"])
    # the lowest rate is the one ``check`` follows, and the limits' own
    line = min(rates, key=lambda ln: ln["rate"])
    assert line["sound_failed"] == []
    assert timed & set(line["four_bits_failed"])
    # faults of the loop's step: only what the timed fit returned can show them
    assert "param_change_gap" in line["half_window_failed"]
    assert line["frozen_leaf_failed"] == ["param_change_gap"]
