"""``BENCHMARK.json``'s ``per_layer``, statically: the cap of 128 entries,
no name twice, every reader module read by some metric, every entry
within the file's limits of form and listing cells that exist and report
the end-to-end metric it ``moves``. With
``test_program_spans.py::test_every_metric_file_names_a_reader_that_exists``
(one ``metrics/<name>.json`` an entry and no other, its reader a module
that loads) it is what a ``benchmark`` PR that retires or re-points an
entry has to keep true (README.md, "Retiring a metric").

A copy of ``benchmark/tests/test_contract.py`` (which runs with the
benchmark's own tests, outside tier-1), so that the driver's command
counts the contract every PR that appends an entry appends under."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CAP = 128

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
PER_LAYER = BENCHMARK["per_layer"]
CELLS = {w["name"] for w in BENCHMARK["workloads"]}
END_TO_END = {m["name"]: m for m in BENCHMARK["end_to_end"]}


def _reports(metric, cell):
    """Whether ``cell`` reports the end-to-end ``metric``: listed under
    it, or the metric lists no cell and so is every cell's."""
    return cell in END_TO_END[metric].get("workloads", CELLS)


def test_the_list_has_room_and_no_name_twice():
    names = [m["name"] for m in PER_LAYER]
    assert 1 <= len(names) <= CAP
    assert len(set(names)) == len(names)
    assert not set(names) & set(END_TO_END)
    # the driver's limit on the file (README.md, "A per-layer metric"): a
    # larger one is refused before a single run, like a 129th entry
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_every_reader_module_is_read_by_some_metric():
    used = set()
    for m in PER_LAYER:
        with open(os.path.join(BENCH, "metrics", f"{m['name']}.json")) as f:
            used.add(json.load(f)["reader"])
    modules = {f[:-len(".py")] for f in os.listdir(os.path.join(BENCH, "readers"))
               if f.endswith(".py") and not f.startswith("_")}
    assert used == modules


@pytest.mark.parametrize("entry", PER_LAYER, ids=lambda m: m["name"])
def test_an_entry_keeps_to_the_form_and_lists_cells_that_can_report_it(entry):
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", entry["name"])
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
    assert 1 <= len(entry["layer"]) <= 200 and "\n" not in entry["layer"]
    assert entry["moves"] in END_TO_END
    # without the key an entry is every cell's that reports what it moves
    cells = entry.get("workloads", ())
    assert "workloads" not in entry or cells
    assert len(set(cells)) == len(cells) and set(cells) <= CELLS
    for cell in cells:
        assert _reports(entry["moves"], cell), (cell, entry["moves"])
    # a share of a roofline or of a peak is named for what it is
    if entry["name"].endswith("_roofline") or "mfu" in entry["name"]:
        assert entry["unit"] == "%" and entry["better"] == "higher"
