"""The switches that are left, in one table: every ``FLINKML_TPU_*``
variable the package reads has its row under "Environment variables" in
``docs/development/overview.md`` (the module that reads it, and for the
ones that choose between duplicate paths the ROADMAP label they wait
under), and no row names a variable that nothing reads."""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "flinkml_tpu")

#: The variables that choose between duplicate paths no cell runs
#: (ROADMAP D2): their rows say where they wait.
DUPLICATE_PATHS = {
    "FLINKML_TPU_GBT_HISTOGRAM", "FLINKML_TPU_ALS_REDUCTION",
    "FLINKML_TPU_W2V_ACCUM", "FLINKML_TPU_EMBEDDING_EXCHANGE",
    "FLINKML_TPU_EMBEDDING_DENSE_VOCAB", "FLINKML_TPU_INT8_MIN_CONST",
    "FLINKML_TPU_DISABLE_FUSION",
}


@functools.lru_cache(maxsize=1)
def package_sources():
    """``{path: text}`` of every Python source of the package (read
    once a process; callers do not write into it)."""
    sources = {}
    for directory, _, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(directory, name)
                with open(path) as f:
                    sources[path] = f.read()
    return sources


def variables_read():
    """``{variable: [modules that name it]}`` over the package."""
    found = {}
    for path, text in package_sources().items():
        for name in set(re.findall(r"FLINKML_TPU_[A-Z0-9_]+", text)):
            found.setdefault(name, []).append(os.path.relpath(path, REPO))
    return found


def _rows():
    with open(os.path.join(REPO, "docs", "development", "overview.md")) as f:
        text = f.read()
    section = text.split("\n## Environment variables\n", 1)[1].split("\n## ")[0]
    rows = {}
    for line in section.splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if line.startswith("| `FLINKML_TPU_"):
            rows[cells[0].strip("`")] = cells
    return rows


@pytest.mark.parametrize("name", sorted(variables_read()))
def test_a_variable_the_package_reads_has_its_row(name):
    variable, read_by, values, default, waits = _rows()[name]
    modules = re.findall(r"`(flinkml_tpu/[\w/]+\.py)`", read_by)
    assert modules and set(modules) <= set(variables_read()[name])
    assert values and default
    if name in DUPLICATE_PATHS:
        assert re.match(r"D2\b", waits) and waits.endswith("no cell")
    else:
        assert waits == ""


def test_no_row_names_a_variable_nothing_reads():
    read = variables_read()
    assert sorted(_rows()) == sorted(read)
    assert len(read) == 14 and DUPLICATE_PATHS <= set(read)
