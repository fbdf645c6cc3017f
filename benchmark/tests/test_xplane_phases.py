"""What PR 49 added for the phases, on the CPU: the wire walk over a
hand-built ``XSpace`` of a few bytes, the exclusive attribution over
plain data, the reader over both, and every new entry of
``BENCHMARK.json`` resolved to its files in each of its cells, where it
reads None without a trace."""

import json
import os

import pytest

from benchmark import run
from benchmark.readers import _xplane_phases as xph
from benchmark.readers import trace_phase_device_time as reader

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


# -- a protobuf writer of four lines, for the test's XSpace -------------------

def varint(n: int) -> bytes:
    out = b""
    while True:
        byte, n = n & 0x7F, n >> 7
        out += bytes([byte | (0x80 if n else 0)])
        if not n:
            return out


def number(field: int, n: int) -> bytes:
    return varint(field << 3) + varint(n)


def nested(field: int, payload: bytes) -> bytes:
    return varint(field << 3 | 2) + varint(len(payload)) + payload


def entry(key: int, value: bytes) -> bytes:
    return number(1, key) + nested(2, value)


def stat_metadata(ident: int, name: str) -> bytes:
    return nested(5, entry(ident, number(1, ident) + nested(2, name.encode())))


def event_metadata(ident: int, name: str, *stats: bytes) -> bytes:
    return nested(4, entry(ident, number(1, ident) + nested(2, name.encode())
                           + b"".join(nested(5, s) for s in stats)))


TF_OP, PROGRAM_ID, A_PATH = 1, 2, 3
#: What parsing would choke on: a field 4 whose length runs past the end.
TRUNCATED_LINE = number(1, 9) + varint(4 << 3 | 2) + varint(5000) + b"\x01\x02"


def device_plane(name: str, program: int, module: str, second_phase: str) -> bytes:
    return nested(1, b"".join([
        number(1, 0),
        nested(2, name.encode()),
        nested(3, TRUNCATED_LINE),
        stat_metadata(TF_OP, "tf_op"),
        stat_metadata(PROGRAM_ID, "program_id"),
        stat_metadata(A_PATH, f"jit(p.12)/while/body/flinkml.{second_phase}/mul:"),
        event_metadata(
            1, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop",
            number(1, TF_OP) + nested(5, b"jit(p.12)/while/body/flinkml.x.a/add:"),
            number(1, PROGRAM_ID) + number(3, program)),
        event_metadata(          # the path as a reference to a stat's name
            2, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1)",
            number(1, TF_OP) + number(7, A_PATH),
            number(1, PROGRAM_ID) + number(3, program)),
        event_metadata(3, "%while.3 = (s32[], f32[8]{0}) while(%tuple.1)",
                       number(1, PROGRAM_ID) + number(3, program)),
        event_metadata(4, module),
        nested(3, TRUNCATED_LINE),
    ]))


HOST_PLANE = nested(1, nested(2, b"/host:CPU") + event_metadata(
    1, "%fusion.9 = f32[] fusion()",
    number(1, TF_OP) + nested(5, b"jit(q)/flinkml.never/add:"),
    number(1, PROGRAM_ID) + number(3, 5)))


def test_the_wire_walk_reads_the_two_maps_and_steps_over_the_lines():
    space = (device_plane("/device:TPU:0", 77, "jit_p.12(77)", "x.b") + HOST_PLANE
             + nested(4, b"a-host-name"))
    assert xph.names(memoryview(space)) == {"p": [
        {"fusion.1": "x.a", "fusion.2": "x.b", "while.3": None}]}
    # descending into a line is what would raise
    with pytest.raises((ValueError, IndexError)):
        list(xph.fields(TRUNCATED_LINE, 0, len(TRUNCATED_LINE)))


def test_a_program_compiled_twice_is_two_maps_and_two_chips_are_one():
    space = (device_plane("/device:TPU:0", 77, "jit_p.12(77)", "x.b")
             + device_plane("/device:TPU:1", 77, "jit_p.12(77)", "x.b")
             + device_plane("/device:TPU:0", 78, "jit_p.12(78)", "x.c"))
    found = xph.names(memoryview(space))
    assert sorted(m["fusion.2"] for m in found["p"]) == ["x.b", "x.c"]
    assert len(found["p"]) == 2


def test_a_module_no_run_names_is_left_out():
    space = device_plane("/device:TPU:0", 77, "ThreadpoolListener::Region", "x.b")
    assert xph.names(memoryview(space)) == {}


@pytest.mark.parametrize("path,phase", [
    ("jit(w2v_sgns_loop.1)/while/body/closed_call/flinkml.w2v.sort/jit(sort)/sort:",
     "w2v.sort"),
    ("jit(p)/flinkml.x.a", "x.a"),
    ("jit(p)/while/body/dot_general:", None),
    ("jit(flinkml.x)/add:", None),
])
def test_the_phase_of_a_path(path, phase):
    assert xph.phase_of(path) == phase


# -- the attribution, on plain data -------------------------------------------

PHASES = {"p": [{"while.3": None, "body.5": None, "fusion.1": "x.a",
                 "fusion.2": "x.b", "copy.4": None}]}


def one_run(at: float):
    """A run of ``p`` from ``at`` to ``at + 100``: a ``while`` over all of
    it but the last 2, a ``body`` inside that, two phased children (25
    and 30 long) with a gap of 10 between them, and a copy after the
    loop's own last 5."""
    return [("while.3", at, at + 98), ("body.5", at + 5, at + 93),
            ("fusion.1", at + 10, at + 35), ("fusion.2", at + 45, at + 75),
            ("copy.4", at + 98, at + 99)]


def test_exclusive_attribution_adds_up_to_the_programs_time():
    ops = {"/device:TPU:0": one_run(0) + one_run(200), "/device:TPU:1": one_run(50)}
    runs = {"/device:TPU:0": [("p", 0, 100), ("q", 120, 180), ("p", 200, 300)],
            "/device:TPU:1": [("p", 50, 150)]}
    got = xph.attribute(ops, runs, (-10, 1000), PHASES)
    assert got == {"p": {"ns": 300.0, "phases": {"x.a": 75.0, "x.b": 90.0}}}


def test_a_run_cut_by_the_windows_edge_counts_for_the_part_inside():
    ops = {"/device:TPU:0": one_run(0)}
    runs = {"/device:TPU:0": [("p", 0, 100)]}
    # the window opens inside fusion.1 and closes inside fusion.2
    got = xph.attribute(ops, runs, (20, 60), PHASES)["p"]
    assert got == {"ns": 40.0, "phases": {"x.a": 15.0, "x.b": 15.0}}
    assert xph.attribute(ops, runs, (100, 200), PHASES) == {}


def test_the_latest_started_of_those_covering_an_instant_takes_it():
    """A child that ends after its parent (clocks round): the parent's
    time ends where the child started, the child's runs to its own end."""
    phases = {"p": [{"outer": "x.a", "inner": "x.b"}]}
    ops = {"c": [("outer", 0, 50), ("inner", 40, 60), ("outer", 70, 80)]}
    got = xph.attribute(ops, {"c": [("p", 0, 100)]}, (0, 100), phases)["p"]
    assert got == {"ns": 100.0, "phases": {"x.a": 50.0, "x.b": 20.0}}


def test_operations_that_start_together_nest_the_longer_outside():
    phases = {"p": [{"while.3": None, "fusion.1": "x.a"}]}
    ops = {"c": [("fusion.1", 0, 30), ("while.3", 0, 90)]}
    got = xph.attribute(ops, {"c": [("p", 0, 100)]}, (0, 100), phases)["p"]
    assert got["phases"] == {"x.a": 30.0}


def test_a_run_takes_the_module_whose_operations_it_ran():
    phases = {"p": [{"fusion.1": "x.a", "fusion.2": "x.b"},
                    {"fusion.1": "x.c", "fusion.7": "x.b"}]}
    ops = {"c": [("fusion.1", 0, 10), ("fusion.2", 10, 20),
                 ("fusion.1", 100, 110), ("fusion.7", 110, 120)]}
    runs = {"c": [("p", 0, 20), ("p", 100, 120)]}
    got = xph.attribute(ops, runs, (0, 200), phases)["p"]
    assert got == {"ns": 40.0, "phases": {"x.a": 10.0, "x.b": 20.0, "x.c": 10.0}}


# -- the reader ---------------------------------------------------------------

def _found(monkeypatch, programs, chips=2):
    monkeypatch.setattr(xph, "this_run",
                        lambda obs: {"chips": chips, "programs": programs})
    return {"trace": {"window_s": 1.0}, "traced_units": {"steps": 5}}


def test_the_reader_means_over_chips_and_divides_by_the_units(monkeypatch):
    obs = _found(monkeypatch, {
        "p": {"ns": 300e6, "phases": {"x.a": 75e6, "x.b": 90e6}},
        "q": {"ns": 100e6, "phases": {"y.a": 100e6}}})
    read = lambda **params: reader.read(params, obs)
    assert read(programs=["p"], phase="x.a", unit="steps") == pytest.approx(7.5)
    assert read(programs=["p"], phase=None, unit="steps") == pytest.approx(13.5)
    assert read(programs=["p"], phase=None, unit=None) == pytest.approx(45.0)
    assert read(programs=["p", "q", "r"], phase=None, unit=None) == pytest.approx(33.75)
    # phases and unphased time add up to the program's time per unit
    assert 7.5 + 9.0 + 13.5 == pytest.approx(300e6 / 2 / 1e6 / 5)
    assert read(programs=["p"], phase="x.a", unit="rounds") is None


def test_the_reader_reads_none_where_the_program_lost_its_names(monkeypatch):
    obs = _found(monkeypatch, {"p": {"ns": 300e6, "phases": {}},
                               "q": {"ns": 100e6, "phases": {"y.a": 100e6}}})
    assert reader.read({"programs": ["p"], "phase": "x.a", "unit": "steps"}, obs) is None
    assert reader.read({"programs": ["p"], "phase": None, "unit": None}, obs) is None
    assert reader.read({"programs": ["q"], "phase": "x.a", "unit": "steps"}, obs) is None
    assert reader.read({"programs": ["r"], "phase": None, "unit": None}, obs) is None


def test_no_trace_no_profile_is_looked_for():
    params = {"programs": ["p"], "phase": "x.a", "unit": "steps"}
    assert reader.read(params, {"trace": None, "traced_units": {"steps": 5}}) is None


# -- the entries --------------------------------------------------------------

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
#: cell -> the phase metrics ISSUE 49 lists for it.
CELLS = {
    "w2v-1bw.fit": ["w2v.draw", "w2v.fetch", "w2v.grads", "w2v.sort", "w2v.update"],
    "fm-criteo.fit": ["fm.lookup", "fm.interaction", "fm.accumulate", "fm.adam"],
    "als-yahoomusic.fit": ["als.fetch", "als.gram", "als.solve"],
    "gbt-airline.fit": ["gbt.gradients", "gbt.histograms", "gbt.splits", "gbt.route"],
    "lr-criteo.fit": ["trainer.sparse_lookup", "trainer.sparse_accumulate"],
    "lr-criteo.fit-cold": ["trainer.sparse_lookup", "trainer.sparse_accumulate"],
    "kmeans-mnist8m.fit": ["kmeans.assign", "kmeans.sums"],
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_new_entries_resolve_to_files_and_read_none_without_a_trace(cell):
    spec = run.load_spec(ROOT, cell)
    mine = {m["name"]: m for m in spec["per_layer"]
            if m["reader"] == "trace_phase_device_time"}
    assert sorted(mine) == sorted(
        [f"{p}_device_ms" for p in CELLS[cell]] + ["device.unphased_share.fit"])
    whole = {m["name"]: m for m in spec["per_layer"]}
    obs = {"trace": None, "traced_units": {}, "units": {}}
    for name, m in mine.items():
        assert (m["source"], m["better"], m["moves"]) == (
            "device_trace", "lower", "fit_samples_per_s")
        assert reader.read(m["params"], obs) is None
        if name == "device.unphased_share.fit":
            assert (m["unit"], m["layer"], m["params"]["unit"]) == ("%", "Device", None)
            continue
        # in the layer and the unit of the program's own device metric
        program = m["params"]["programs"]
        own = next(o for o in whole.values()
                   if o["name"].endswith(("device_ms", "device_ms_per_step"))
                   and o["reader"] != "trace_phase_device_time"
                   and (set(o["params"].get("programs", ())) >= set(program)
                        or o["params"].get("span", "").startswith(name.split(".")[0])))
        assert m["layer"] == own["layer"] and m["unit"] == "ms"
        assert m["params"]["unit"] == own["params"]["unit"]


def test_the_new_entries_were_appended_in_their_order_and_list_their_cells():
    """ISSUE 49's twenty-one, in the order it appended them (later
    issues append their own after or between, so none is counted from
    the end), each listing every cell whose program has its phase."""
    own = list(dict.fromkeys(f"{p}_device_ms" for c in CELLS for p in CELLS[c]))
    own.append("device.unphased_share.fit")
    assert len(own) == 21
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    at = [names.index(n) for n in own]
    assert at == sorted(at)
    for n, i in zip(own, at):
        entry = BENCHMARK["per_layer"][i]
        with open(os.path.join(ROOT, "benchmark", "metrics", f"{n}.json")) as f:
            assert json.load(f)["reader"] == "trace_phase_device_time"
        cells = {c for c in CELLS if n[:-len("_device_ms")] in CELLS[c]} or set(CELLS)
        assert cells <= set(entry["workloads"]), n
