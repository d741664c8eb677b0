"""BENCHMARK.json and the data files it names: cells, configurations,
traffic mixes, metrics and their readers, the table of peaks.

A cell is one entry of ``workloads``: it names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``).
A per-layer metric is ``metrics/<name>.json``, which names a reader module
``readers/<reader>.py`` and the arguments it is called with. Nothing here
imports the program.
"""
import functools
import importlib
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
MANIFEST = os.path.join(REPO, "BENCHMARK.json")


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@functools.cache
def manifest() -> dict:
    with open(MANIFEST) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<traffic>.json
    end_to_end: tuple   # names of the end-to-end metrics this cell reports
    per_layer: tuple    # names of the per-layer metrics read in this cell


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str) -> Cell:
    m = manifest()
    entries = [w for w in m["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in m["workloads"])
        raise KeyError(f"no cell {name!r} in BENCHMARK.json (cells: {known})")
    w = entries[0]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=_load("configs", w["config"] + ".json"),
        traffic=_load("traffic", w["traffic"] + ".json"),
        end_to_end=tuple(x["name"] for x in m["end_to_end"]
                         if _in_cell(x, name)),
        per_layer=tuple(x["name"] for x in m["per_layer"]
                        if _in_cell(x, name)))


def metric_unit(name: str) -> str:
    m = manifest()
    for x in m["end_to_end"] + m["per_layer"]:
        if x["name"] == name:
            return x["unit"]
    raise KeyError(name)


def load_reader(metric: str):
    """(read function, arguments) of a per-layer metric."""
    spec = _load("metrics", metric + ".json")
    module = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return module.read, spec.get("args", {})


def peaks(device_kind: str) -> dict:
    table = _load("peaks.json")["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({', '.join(table)}): add its "
                       f"published peaks with their source, do not guess")
    return table[device_kind]
