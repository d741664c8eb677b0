"""BENCHMARK.json against the files it names and the contract's limits."""
import importlib
import json
import os
import re

import pytest

from benchmark import cells

M = cells.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert M["command"] == ["python3", "benchmark/run.py"]
    assert os.path.getsize(cells.MANIFEST) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_with_its_configuration_traffic_and_reference(cell):
    c = cells.load_cell(cell)
    entry = next(x for x in M["configs"] if x["name"] == c.config_name)
    assert entry["source"] == c.config["source"]
    assert c.chips == c.config["chips"]
    assert set(entry["reduced"]) == set(c.config["reduced"])
    assert os.path.exists(os.path.join(cells.REPO, entry["file"]))
    ref = importlib.import_module(
        f"benchmark.references.{c.traffic['reference']}")
    assert callable(ref.reference)
    for table in c.traffic["columns"]:    # each has a generator of its name
        assert callable(importlib.import_module(
            f"benchmark.tables.{table}").generate)
    assert set(c.traffic["limits"]) == {"max_rel_err", "exact_mismatches",
                                        "failed_queries"}
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert len(c.per_layer) >= 1


def test_every_configuration_is_used_and_a_pair_appears_once():
    assert {c["name"] for c in M["configs"]} == \
        {w["config"] for w in M["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    names = [x["name"] for x in METRICS] + CELLS \
        + [c["name"] for c in M["configs"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(metric):
    read, args = cells.load_reader(metric["name"])
    assert callable(read) and isinstance(args, dict)
    moved = next(x for x in M["end_to_end"] if x["name"] == metric["moves"])
    assert set(cells_of(metric)) <= set(cells_of(moved))
    assert set(cells_of(metric)) <= set(CELLS)
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in {"device_trace", "program_span",
                                "program_counter", "host_clock"}


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric_has_a_bound(metric):
    assert 0.01 <= metric["bound"] <= 0.25
    assert metric["source"] in {"host_clock", "device_trace"}
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_names_and_units_use_the_allowed_characters(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in {"lower", "higher"}


@pytest.mark.parametrize("entry", M["workloads"] + M["configs"],
                         ids=lambda e: e["name"])
def test_entries_are_named_and_explained_on_one_line(entry):
    assert NAME.match(entry["name"])
    for key in ("why", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    for key in entry.get("reduced", ()):
        assert NAME.match(key)


def test_files_under_paths_are_named_from_the_allowed_characters():
    for path in M["paths"]:
        for d, dirs, files in os.walk(os.path.join(cells.REPO, path)):
            dirs[:] = [x for x in dirs if not x.startswith(".")
                       and x != "__pycache__"]
            for f in files:
                if not f.endswith(".pyc"):
                    assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_nothing_under_benchmark_imports_the_smoke_or_the_old_bench():
    for d, _, files in os.walk(os.path.join(cells.REPO, "benchmark")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    src = fh.read()
                assert not re.search(
                    r"^\s*(import|from)\s+(chip_smoke|bench)\b", src, re.M), f


def test_an_unknown_device_kind_is_an_error():
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        cells.peaks("TPU v9 imaginary")


def test_metric_files_and_readers_all_belong_to_a_metric():
    here = os.path.join(cells.REPO, "benchmark")
    named = {m["name"] + ".json" for m in M["per_layer"]}
    assert set(os.listdir(os.path.join(here, "metrics"))) == named
    used = set()
    for f in named:
        with open(os.path.join(here, "metrics", f)) as fh:
            used.add(json.load(fh)["reader"] + ".py")
    have = {f for f in os.listdir(os.path.join(here, "readers"))
            if f.endswith(".py") and f != "__init__.py"}
    assert have == used
