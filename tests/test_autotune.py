"""Autotune: tuning-table semantics, lookup precedence, hysteresis,
consumers at every key-construction site, and the committed table's
measured-values contract (ISSUE 11)."""

import json
import os

import numpy as np
import pytest

from flinkml_tpu.autotune import (
    DEFAULT_TABLE_PATH,
    KNOWN_KNOBS,
    TuningTable,
    load_table,
    mesh_key,
    tuned_default,
)
from flinkml_tpu.autotune.search import (
    RATIO_FLOOR,
    STATIC_DEFAULTS,
    order_presets,
    settle,
)
from flinkml_tpu.autotune.table import ENV_DISABLE_VAR, ENV_TABLE_VAR


def _write_table(tmp_path, knobs, mesh=None):
    table = TuningTable()
    mesh = mesh or mesh_key()
    for knob, value in knobs.items():
        table.set_knob(mesh, knob, value,
                       candidates={"a": 1.0, "b": 2.0},
                       source="test")
    path = str(tmp_path / "table.json")
    table.save(path)
    return path


@pytest.fixture
def tuned(tmp_path, monkeypatch):
    """Point the process at a throwaway tuning table."""
    def point_at(knobs, mesh=None):
        monkeypatch.setenv(ENV_TABLE_VAR, _write_table(tmp_path, knobs, mesh))
    return point_at


# -- table semantics ---------------------------------------------------------


def test_table_roundtrip_and_check(tmp_path):
    table = TuningTable()
    table.set_knob("cpu/cpu/8", "gbt_histogram", "cumsum",
                   candidates={"segment": 1.0, "cumsum": 2.0},
                   source="test")
    path = str(tmp_path / "t.json")
    table.save(path)
    loaded = load_table(path)
    assert loaded.value("cpu/cpu/8", "gbt_histogram") == "cumsum"
    assert loaded.check() == []
    rec = loaded.record("cpu/cpu/8", "gbt_histogram")
    assert rec["candidates"] == {"segment": 1.0, "cumsum": 2.0}
    assert rec["source"] == "test"


def test_table_check_flags_problems(tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as fh:
        json.dump({
            "version": 1,
            "entries": {
                "cpu/cpu/8": {
                    "not_a_knob": {"value": 1, "candidates": {"x": 1.0},
                                   "measured_at": "", "source": "",
                                   "unit": ""},
                    "gbt_histogram": {"value": "cumsum", "candidates": {},
                                      "measured_at": "", "source": "",
                                      "unit": ""},
                },
                "not-a-mesh-key": {},
            },
        }, fh)
    problems = load_table(path).check()
    assert any("unknown knob" in p for p in problems)
    assert any("measured, not guessed" in p for p in problems)
    assert any("bad mesh key" in p for p in problems)


def test_set_knob_refuses_unknown_knob():
    with pytest.raises(ValueError, match="unknown tuning knob"):
        TuningTable().set_knob("cpu/cpu/8", "typo_knob", 1)


def test_unreadable_table_degrades_to_empty(tmp_path, monkeypatch):
    path = tmp_path / "garbage.json"
    path.write_text("{not json")
    monkeypatch.setenv(ENV_TABLE_VAR, str(path))
    assert tuned_default("gbt_histogram", "segment") == "segment"


# -- lookup precedence -------------------------------------------------------


def test_tuned_default_precedence(tuned, monkeypatch):
    tuned({"gbt_histogram": "cumsum"})
    assert tuned_default("gbt_histogram", "segment") == "cumsum"
    # FLINKML_TPU_AUTOTUNE=0 turns the table layer off.
    monkeypatch.setenv(ENV_DISABLE_VAR, "0")
    assert tuned_default("gbt_histogram", "segment") == "segment"
    monkeypatch.delenv(ENV_DISABLE_VAR)
    # a value outside `allowed` degrades to the fallback, loudly-once.
    assert tuned_default("gbt_histogram", "segment",
                         allowed=("segment",)) == "segment"
    # another mesh's entry is invisible here.
    tuned({"gbt_histogram": "cumsum"}, mesh="tpu/TPU_v4/8")
    assert tuned_default("gbt_histogram", "segment") == "segment"


def test_gates_consult_table_env_wins(tuned, monkeypatch):
    from flinkml_tpu.models.als import _als_layout
    from flinkml_tpu.models.gbt import _hist_layout
    from flinkml_tpu.models.word2vec import _w2v_accum

    tuned({
        "gbt_histogram": "cumsum",
        "als_reduction": "cumsum",
        "w2v_accum": "onehot",
    })
    assert _hist_layout() == "cumsum"
    assert _als_layout() == "cumsum"
    assert _w2v_accum() == "onehot"
    # the explicit env gate beats the table everywhere.
    monkeypatch.setenv("FLINKML_TPU_GBT_HISTOGRAM", "segment")
    monkeypatch.setenv("FLINKML_TPU_ALS_REDUCTION", "segment")
    monkeypatch.setenv("FLINKML_TPU_W2V_ACCUM", "scatter")
    assert _hist_layout() == "segment"
    assert _als_layout() == "segment"
    assert _w2v_accum() == "scatter"


def test_infer_plan_consults_measured_order(tuned):
    from flinkml_tpu.sharding.plan import (
        BATCH_PARALLEL,
        FSDP,
        infer_plan,
    )

    shapes = {"coef": (64,)}
    mesh = {"data": 2, "fsdp": 4}
    # Static order: batch_parallel fits -> wins.
    assert infer_plan(mesh, shapes, hbm_budget_bytes=1 << 20).name == \
        "batch_parallel"
    # A measured order promoting fsdp flips the default choice...
    tuned({"infer_plan_order": ["fsdp", "batch_parallel", "fsdp_tp"]})
    assert infer_plan(mesh, shapes, hbm_budget_bytes=1 << 20).name == "fsdp"
    # ...while explicit candidates are untouched by the table.
    assert infer_plan(
        mesh, shapes, hbm_budget_bytes=1 << 20,
        candidates=(BATCH_PARALLEL, FSDP),
    ).name == "batch_parallel"


def test_serving_config_consults_table(tuned):
    from flinkml_tpu.serving.engine import ServingConfig, ServingEngine
    from flinkml_tpu.table import Table

    tuned({"serving_max_batch_rows": 512, "serving_window_ms": 1.5})

    class _Identity:
        def transform(self, table):
            return (table.with_column(
                "out", np.asarray(table.column("features")) * 2.0
            ),)

    example = Table({"features": np.ones((4, 2))})
    engine = ServingEngine(_Identity(), example, name="tuned-cfg")
    assert engine.config.max_batch_rows == 512
    assert engine.config.max_wait_ms == 1.5
    # explicit values always win over the table.
    engine2 = ServingEngine(
        _Identity(), example,
        ServingConfig(max_batch_rows=64, max_wait_ms=3.0),
        name="explicit-cfg",
    )
    assert engine2.config.max_batch_rows == 64
    assert engine2.config.max_wait_ms == 3.0


# -- hysteresis --------------------------------------------------------------


def test_settle_hysteresis():
    # within the floor: incumbent keeps the seat (noise cannot flip).
    assert settle("gbt_histogram",
                  {"segment": 100.0, "cumsum": 105.0}) == "segment"
    # decisive win: challenger takes it.
    assert settle("gbt_histogram",
                  {"segment": 100.0, "cumsum": 100.0 * RATIO_FLOOR * 1.05}
                  ) == "cumsum"
    # numeric knobs keep their type.
    assert settle("serving_max_batch_rows",
                  {"1024": 100.0, "2048": 200.0}) == 2048
    assert settle("serving_window_ms",
                  {"2.0": 100.0, "1.0": 101.0}) == 2.0
    # a COMMITTED winner defends the seat, not the static default: a
    # near-floor measurement cannot flip-flop it back (reverting needs
    # its own decisive win).
    assert settle("gbt_histogram",
                  {"segment": 105.0, "cumsum": 100.0},
                  incumbent="cumsum") == "cumsum"
    assert settle("gbt_histogram",
                  {"segment": 100.0 * RATIO_FLOOR * 1.05, "cumsum": 100.0},
                  incumbent="cumsum") == "segment"


def test_order_presets_promotion():
    static = STATIC_DEFAULTS["infer_plan_order"]
    # ties / within-floor keep the static (cheapest-communication) order
    assert order_presets(
        {"batch_parallel": 100.0, "fsdp": 105.0, "fsdp_tp": 50.0}
    ) == static
    # a decisive fsdp win promotes it past batch_parallel only
    assert order_presets(
        {"batch_parallel": 100.0, "fsdp": 150.0, "fsdp_tp": 50.0}
    ) == ["fsdp", "batch_parallel", "fsdp_tp"]


# -- the committed table -----------------------------------------------------


def test_committed_table_has_measured_values_for_this_mesh():
    """The acceptance pin: the committed table carries MEASURED (not
    guessed) values — winner + candidate measurements — for the three
    sort-class cumsum defaults, the serving bucket/window, and the
    infer_plan order, on the CI mesh (the 8-virtual-device CPU host the
    whole suite runs on)."""
    table = load_table()
    assert table.check() == []
    mesh = mesh_key()
    for knob in KNOWN_KNOBS:
        rec = table.record(mesh, knob)
        assert rec is not None, (
            f"committed tuning table has no {knob!r} entry for mesh "
            f"{mesh!r} — run `python -m flinkml_tpu.autotune --commit`"
        )
        assert rec["candidates"], f"{knob}: no measured candidates"
        assert rec["measured_at"], knob
    # The three sort-class knobs each measured every landed layout.
    assert set(table.record(mesh, "gbt_histogram")["candidates"]) == \
        {"segment", "cumsum"}
    assert set(table.record(mesh, "als_reduction")["candidates"]) == \
        {"segment", "cumsum"}
    assert set(table.record(mesh, "w2v_accum")["candidates"]) == \
        {"scatter", "onehot"}


def test_the_knob_sets_agree():
    """One set of knobs in four places: the search's static defaults,
    its measurers, the table's unit map and the committed ``cpu/cpu/8``
    entry. Nine of them, none a kernel's backend."""
    from flinkml_tpu.autotune.search import MEASURERS

    with open(DEFAULT_TABLE_PATH) as f:
        committed = json.load(f)["entries"]
    assert list(committed) == ["cpu/cpu/8"]
    names = set(STATIC_DEFAULTS)
    assert names == set(MEASURERS) == set(KNOWN_KNOBS) == set(
        committed["cpu/cpu/8"])
    assert len(names) == 9
    assert not [name for name in names if "backend" in name]


def test_quick_search_smoke(tmp_path):
    """The search harness itself, smoke-size, on one cheap knob — the
    full run is `python -m flinkml_tpu.autotune --commit`."""
    from flinkml_tpu.autotune.search import apply_results, search_knobs

    results = search_knobs(["infer_plan_order"], quick=True)
    assert set(results) == {"infer_plan_order"}
    rec = results["infer_plan_order"]
    assert set(rec["candidates"]) == set(STATIC_DEFAULTS["infer_plan_order"])
    assert all(v > 0 for v in rec["candidates"].values())
    table = apply_results(TuningTable(), results, mesh="cpu/cpu/8")
    path = table.save(str(tmp_path / "out.json"))
    assert load_table(path).check() == []
