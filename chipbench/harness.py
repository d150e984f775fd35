"""Finds a cell's parts by name and turns a run into its result line.

Nothing here knows a particular cell, configuration, traffic mix or
metric: ``BENCHMARK.json`` names them, and each is a file of its own --
``configs/<name>.json``, ``traffic/<name>.json``, ``drivers/<driver>.py``
(the entry point a configuration calls) and ``metrics/<metric>.py`` (a
``read(run)`` that returns the metric's value, or ``None`` where the run
has nothing for it to read, in which case the metric is left out).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> dict:
    return load_json(os.path.join(HERE, "configs", f"{name}.json"))


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, cell_name: str, section: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    cases: list  # traffic.generate.Case, by case position
    record: dict  # what the driver's window returned
    setup_s: float
    refs: list  # reference features by case position
    summary: object = None  # trace.Summary of a traced run
    peaks: dict = None  # published peaks of the device


def read_metrics(run: Run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in chipbench/peaks.json") from None
