"""A benchmark cell and the files it is made of, found by name.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
configuration is ``chipbench/configs/<config>.json``, the traffic mix
``chipbench/traffic/<traffic>.json``, the limits of its comparison
``chipbench/limits/<cell>.json`` and each per-layer metric a reader
``chipbench/metrics/<metric>.py``. Adding a cell adds files and entries;
nothing here names a cell.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load(name: str) -> Cell:
    bench = _read(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ValueError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _read(ROOT / conf["file"])
    if config["chips"] != entry["chips"]:
        raise ValueError(f"{name}: the cell asks for {entry['chips']} chips, its "
                         f"configuration is laid out on {config['chips']}")

    # a metric with a "workloads" key is reported in those cells only
    e2e = tuple(m for m in bench["end_to_end"] if name in m.get("workloads", [name]))
    moved = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if name in m.get("workloads", [name]) and m["moves"] in moved)
    return Cell(
        name=name, chips=entry["chips"], config=config,
        traffic=_read(HERE / "traffic" / f"{entry['traffic']}.json"),
        limits=_read(HERE / "limits" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer,
    )


def metric_reader(name: str) -> ModuleType:
    """The module ``chipbench/metrics/<name>.py``; its ``read(run)`` returns
    the metric's value, or None where the run had nothing to read."""
    from chipbench.model import load_module

    return load_module(f"chipbench.metrics.{name}", HERE / "metrics" / f"{name}.py")
