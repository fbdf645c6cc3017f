"""Tests for the observability utilities (SURVEY.md §5 tracing/metrics)."""

import time

import numpy as np

from flinkml_tpu.iteration import IterationConfig, TerminateOnMaxIter, iterate
from flinkml_tpu.utils import (
    EpochMetricsListener,
    MetricsRegistry,
    span,
    trace,
)


def test_counter_gauge_meter_history():
    reg = MetricsRegistry()
    g = reg.group("op")
    assert g.counter("records", 3) == 3
    assert g.counter("records", 2) == 5
    g.gauge("epoch", 7)
    g.record("loss", 0.5)
    g.record("loss", 0.25)
    m = g.meter("rows")
    m.mark(100, now=0.0)
    m.mark(100, now=1.0)
    snap = reg.snapshot()["op"]
    assert snap["counters"]["records"] == 5
    assert snap["gauges"]["epoch"] == 7
    assert snap["histories"]["loss"] == [0.5, 0.25]
    assert abs(snap["meters"]["rows"] - 100.0) < 1e-9


def test_registry_reuses_groups_and_dumps_json():
    reg = MetricsRegistry()
    assert reg.group("a") is reg.group("a")
    reg.group("a").counter("c")
    assert '"c": 1' in reg.dump_json().replace("1.0", "1")
    reg.reset()
    assert reg.snapshot() == {}


def test_epoch_metrics_listener_in_iterate():
    reg = MetricsRegistry()
    listener = EpochMetricsListener(
        group=reg.group("train"), samples_per_epoch=128
    )

    def step(state, epoch):
        return state + 1, None

    result = iterate(
        step, 0, config=IterationConfig(TerminateOnMaxIter(5)),
        listeners=[listener],
    )
    snap = reg.snapshot()["train"]
    assert result.epochs == 5
    assert snap["counters"]["epochs"] == 5
    assert len(snap["histories"]["epoch_seconds"]) == 5
    assert snap["gauges"]["total_seconds"] > 0
    assert snap["gauges"]["samples_per_sec"] > 0


def test_trace_context_is_safe_without_profiler(tmp_path):
    # Must not raise even if the backend can't start a trace.
    with trace(str(tmp_path)):
        x = np.arange(10).sum()
    assert x == 45


def test_span_context():
    from flinkml_tpu.utils import metrics

    before = metrics.group("span").snapshot()["counters"].get("my-region.calls", 0)
    with span("my-region"):
        pass
    after = metrics.group("span").snapshot()["counters"]["my-region.calls"]
    assert after == before + 1


# -- RowReservoir ------------------------------------------------------------

def test_row_reservoir_uniform_and_deterministic():
    from flinkml_tpu.utils.sampling import RowReservoir

    # Fill phase: capacity >= stream -> the sample IS the stream, in order.
    r = RowReservoir(100, seed=0)
    block = np.arange(30, dtype=np.float64).reshape(10, 3)
    r.add(block)
    np.testing.assert_array_equal(r.sample(), block)
    assert r.rows_seen == 10

    # Replacement phase: bounded size, deterministic for a fixed seed,
    # and approximately uniform over the stream.
    def run(seed):
        rr = RowReservoir(64, seed=seed)
        for s in range(50):
            rr.add(np.arange(s * 100, (s + 1) * 100, dtype=np.float64)[:, None])
        return rr.sample()

    a, b = run(1), run(1)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (64, 1)
    # Uniformity: the sample mean of row ids is near the stream mean.
    mean = float(np.mean(run(2)))
    assert abs(mean - 2499.5) < 600, mean


def test_render_text_prometheus_exposition():
    reg = MetricsRegistry()
    g = reg.group("serving.demo")
    g.counter("requests", 3)
    g.gauge("queue_depth", 2)
    g.gauge("label", "not-a-number")  # skipped: non-numeric
    m = g.meter("rows")
    m.mark(100, now=0.0)
    m.mark(100, now=1.0)
    reg.group("train.lr").counter("requests", 7)  # same metric, 2nd group
    text = reg.render_text()
    lines = text.splitlines()
    assert "# TYPE flinkml_requests counter" in lines
    assert 'flinkml_requests{group="serving.demo"} 3' in lines
    assert 'flinkml_requests{group="train.lr"} 7' in lines
    assert "# TYPE flinkml_queue_depth gauge" in lines
    assert 'flinkml_queue_depth{group="serving.demo"} 2' in lines
    assert any(l.startswith('flinkml_rows_rate{group="serving.demo"}')
               for l in lines)
    assert "not-a-number" not in text
    # TYPE lines precede their samples; output is deterministic.
    assert text == reg.render_text()
    assert reg.render_text().endswith("\n")


def test_render_text_sanitizes_names_and_default_registry():
    from flinkml_tpu.utils import default_registry, metrics

    assert default_registry() is metrics
    reg = MetricsRegistry()
    reg.group("g").counter("weird name-1.x", 1)
    text = reg.render_text()
    assert "flinkml_weird_name_1_x" in text
    assert reg.render_text() == text
    assert MetricsRegistry().render_text() == ""
    # Label VALUES escape quotes/backslashes/newlines (exposition format).
    reg2 = MetricsRegistry()
    reg2.group('serving.a"b\\c').counter("requests", 1)
    assert '{group="serving.a\\"b\\\\c"}' in reg2.render_text()


def test_render_text_replica_labels_aggregate():
    """Per-replica groups share ONE metric family distinguished by a
    ``replica`` label (the serving pool's exposition) instead of
    colliding in a flat namespace; label-less groups are unchanged."""
    reg = MetricsRegistry()
    for i, depth in enumerate((2, 5)):
        g = reg.group("serving.pool", labels={"replica": f"r{i}"})
        g.gauge("queue_depth", depth)
        g.counter("requests", 10 * (i + 1))
    reg.group("serving.pool").counter("requests", 7)  # pool-level, no label
    text = reg.render_text()
    lines = text.splitlines()
    assert lines.count("# TYPE flinkml_queue_depth gauge") == 1
    assert 'flinkml_queue_depth{group="serving.pool",replica="r0"} 2' in lines
    assert 'flinkml_queue_depth{group="serving.pool",replica="r1"} 5' in lines
    assert 'flinkml_requests{group="serving.pool",replica="r0"} 10' in lines
    assert 'flinkml_requests{group="serving.pool",replica="r1"} 20' in lines
    assert 'flinkml_requests{group="serving.pool"} 7' in lines
    # Distinct label sets are distinct groups; same set is the same one.
    a = reg.group("serving.pool", labels={"replica": "r0"})
    assert a is reg.group("serving.pool", labels={"replica": "r0"})
    assert a is not reg.group("serving.pool")
    # snapshot() keys label-qualified names; plain names stay plain.
    snap = reg.snapshot()
    assert snap['serving.pool{replica="r0"}']["gauges"]["queue_depth"] == 2
    assert snap["serving.pool"]["counters"]["requests"] == 7
    assert text == reg.render_text()  # deterministic


def test_render_text_full_precision_and_type_collisions():
    # Counters keep full precision (no %g truncation past 6 sig digits).
    reg = MetricsRegistry()
    reg.group("g").counter("requests", 1_234_567)
    assert 'flinkml_requests{group="g"} 1234567' in reg.render_text()
    # The same metric name as counter in one group, gauge in another:
    # one family per type (the later kind gets a kind-suffixed family),
    # never a mistyped sample under a single TYPE line.
    reg2 = MetricsRegistry()
    reg2.group("a").counter("depth", 2)
    reg2.group("b").gauge("depth", 5)
    text = reg2.render_text()
    assert "# TYPE flinkml_depth counter" in text
    assert 'flinkml_depth{group="a"} 2' in text
    assert "# TYPE flinkml_depth_gauge gauge" in text
    assert 'flinkml_depth_gauge{group="b"} 5' in text


# -- rank-tagged logging (ISSUE 4 satellite) ---------------------------------

def test_rank_tagged_logger(caplog):
    import logging

    from flinkml_tpu.utils import logging as flog

    log = flog.get_logger("testrank")
    with caplog.at_level(logging.INFO, logger="flinkml_tpu.testrank"):
        log.info("hello %s", "world")
    assert caplog.records[-1].getMessage() == "[rank 0/1] hello world"
    # Pinning the rank changes the tag; restore for other tests.
    try:
        flog.set_rank(3, 8)
        assert flog.rank_tag() == "[rank 3/8]"
    finally:
        flog._RANK = None
    assert flog.rank_tag() == "[rank 0/1]"


def test_logger_namespace_and_console_handler_idempotent():
    import logging

    from flinkml_tpu.utils import logging as flog

    assert flog.get_logger("x").logger.name == "flinkml_tpu.x"
    assert flog.get_logger("flinkml_tpu.y").logger.name == "flinkml_tpu.y"
    root = logging.getLogger("flinkml_tpu")
    before = list(root.handlers)
    try:
        h1 = flog.enable_console(logging.WARNING)
        h2 = flog.enable_console(logging.INFO)
        assert h1 is h2  # reused, not stacked
        assert h2.level == logging.INFO
    finally:
        root.handlers = before
        root.setLevel(logging.NOTSET)


def test_checkpoint_operations_emit_logs(tmp_path, caplog):
    import logging

    import numpy as np

    from flinkml_tpu.iteration import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), max_to_keep=1)
    with caplog.at_level(logging.INFO, logger="flinkml_tpu.checkpoint"):
        mgr.save({"w": np.ones(2)}, 1)
        mgr.save({"w": np.ones(2)}, 2)  # prunes epoch 1
    text = " ".join(r.getMessage() for r in caplog.records)
    assert "checkpoint committed: epoch 1" in text
    assert "pruning checkpoint epoch 1" in text
    assert "[rank 0/1]" in text
