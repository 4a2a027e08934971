"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); ``workloads/<cell>.json`` holds what is the
cell's own (engine settings, latency limits, the correctness limit); each
per-layer metric is read by ``metrics/<metric>.py``.  Adding a cell or a
metric adds files and entries, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    entry: dict            # the cell's entry in BENCHMARK.json
    config: dict           # configs/<config>.json
    traffic: dict          # traffic/<traffic>.json
    own: dict              # workloads/<cell>.json
    end_to_end: list       # the end-to-end metrics this cell reports
    per_layer: list        # the per-layer metrics this cell reports


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """Whether ``cell`` reports ``metric``: listed cells where the metric
    names them, else every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def cell(name: str) -> Cell:
    b = benchmark()
    entries = {w["name"]: w for w in b["workloads"]}
    if name not in entries:
        raise KeyError(f"no cell {name!r}; cells: {sorted(entries)}")
    entry = entries[name]
    e2e = [m for m in b["end_to_end"] if reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per = [m for m in b["per_layer"] if reports(m, name, names)]
    return Cell(name=name, entry=entry,
                config=_load(BENCH / "configs" / f"{entry['config']}.json"),
                traffic=_load(BENCH / "traffic" / f"{entry['traffic']}.json"),
                own=_load(BENCH / "workloads" / f"{name}.json"),
                end_to_end=e2e, per_layer=per)


def metric_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    table = _load(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
