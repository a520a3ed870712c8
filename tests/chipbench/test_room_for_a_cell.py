"""A configuration and a cell are added by new files and appended entries
alone: no test of the benchmark pins the end of `BENCHMARK.json`'s lists.

Every test of this directory that reads `BENCHMARK.json`, and needs no
fixture (those are whole runs, not lists), runs again here against a copy
of the checkout whose `BENCHMARK.json` has the toy `rehearsal_grid_ksp2`
appended as a configuration and its cell `rehearsal_grid_ksp2.metric_flaps`
as a cell, joined to every per-layer list that `grid10000.metric_flaps` is
on, save those of metrics that move the 95th percentile, which the toy does
not report. A test that pins a list's last entry or its length fails here."""

import glob
import importlib
import inspect
import itertools
import json
import os

import pytest
from _pytest.mark.structures import ParameterSet

from chipbench import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG, CELL, GRID = "rehearsal_grid_ksp2", "rehearsal_grid_ksp2.metric_flaps", "grid10000.metric_flaps"
READS_THE_LISTS = ("BENCHMARK.json", "_bench()", "resolve_cell(")


def _cases(fn):
    """Every argument set of `fn`'s `parametrize` marks, as keyword dicts."""
    axes = []
    for mark in getattr(fn, "pytestmark", []):
        if mark.name != "parametrize":
            continue
        names, values = mark.args[:2]
        if isinstance(names, str):
            names = [n.strip() for n in names.split(",") if n.strip()]
        rows = []
        for value in values:
            if isinstance(value, ParameterSet):
                value = value.values
            elif len(names) == 1:
                value = (value,)
            rows.append(dict(zip(names, value)))
        axes.append(rows)
    for combo in itertools.product(*axes):
        yield {k: v for row in combo for k, v in row.items()}


def _list_tests():
    me = os.path.splitext(os.path.basename(__file__))[0]
    for path in sorted(glob.glob(os.path.join(HERE, "test_*.py"))):
        name = os.path.splitext(os.path.basename(path))[0]
        if name == me:
            continue
        module = importlib.import_module(name)
        for fname, fn in sorted(vars(module).items()):
            if not (fname.startswith("test_") and inspect.isfunction(fn)):
                continue
            if not any(word in inspect.getsource(fn) for word in READS_THE_LISTS):
                continue
            cases = list(_cases(fn))
            if set(inspect.signature(fn).parameters) - set(cases[0]):
                continue  # a fixture: a whole run, not a list
            for i, kwargs in enumerate(cases):
                yield pytest.param(module, fn, kwargs, id=f"{name}.{fname}[{i}]")


CONFIG_ENTRY = {
    "name": CONFIG,
    "source": "upstream facebook/openr openr/decision/tests/DecisionBenchmark.cpp:640-728,806-813: "
              "the KSP2 grid sweep, KSP2_ED_ECMP over SR-MPLS",
    "file": f"chipbench/configs/{CONFIG}.json",
    "reduced": ["topology"],
    "why": "8 x 8 grid, every /24 over SR-MPLS with KSP2_ED_ECMP: the label routes and the second path set",
}
CELL_ENTRY = {
    "name": CELL, "config": CONFIG, "traffic": "metric_flaps", "chips": 1,
    "why": "links 1-5 of row 0 and of column 0, one event outstanding: KSP2 routes rebuilt every event",
}


def _bench(root):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _appended():
    bench = _bench(ROOT)
    bench["configs"].append(CONFIG_ENTRY)
    bench["workloads"].append(CELL_ENTRY)
    for m in bench["per_layer"]:
        if GRID in m.get("workloads", []) and not m["moves"].endswith(".p95"):
            m["workloads"].append(CELL)
    return bench


@pytest.fixture(scope="module")
def appended_root(tmp_path_factory):
    """A checkout that differs from this one in its `BENCHMARK.json` alone."""
    root = tmp_path_factory.mktemp("appended")
    for entry in os.listdir(ROOT):
        if entry != "BENCHMARK.json":
            os.symlink(os.path.join(ROOT, entry), root / entry)
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump(_appended(), fh, indent=1)
    return str(root)


def test_the_copy_appends_a_configuration_a_cell_and_nothing_else(appended_root, monkeypatch):
    bench, copy = _bench(ROOT), _bench(appended_root)
    assert copy["configs"] == bench["configs"] + [CONFIG_ENTRY]
    assert copy["workloads"] == bench["workloads"] + [CELL_ENTRY]
    assert copy["end_to_end"] == bench["end_to_end"]
    joined = 0
    for old, new in zip(bench["per_layer"], copy["per_layer"], strict=True):
        cells = old.get("workloads", [])
        assert new["workloads"] in (cells, cells + [CELL])
        joined += new["workloads"] != cells
    assert joined > 40
    assert any(m["name"] == "graph_recompiles_in_window" and CELL in m["workloads"]
               for m in copy["per_layer"])
    # the cell resolves by its existing files, and reports what it is listed on
    monkeypatch.setattr(bench_run, "ROOT", appended_root)
    cell = bench_run.resolve_cell(CELL)
    assert cell["config"] == CONFIG and cell["per_layer"]
    assert "event_to_fib_ms.p95" not in {m["name"] for m in cell["end_to_end"]}


@pytest.mark.parametrize("module, fn, kwargs", list(_list_tests()))
def test_every_list_test_passes_with_a_configuration_and_a_cell_appended(
    module, fn, kwargs, appended_root, monkeypatch
):
    for reader in (module, bench_run):
        if hasattr(reader, "ROOT"):
            monkeypatch.setattr(reader, "ROOT", appended_root)
    fn(**kwargs)
