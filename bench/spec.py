"""Finds what a cell names, by name: its configuration and traffic files,
the reference, the data generator, the traffic driver and each metric's
reader. Nothing here knows a particular cell.

  BENCHMARK.json                   cells, configurations, metrics
  bench/configs/<config>.json      sizes, build and server settings, limits
  bench/traffic/<traffic>.json     a traffic mix: ``kind`` and parameters
  bench/loads/<kind>.py            the driver of that kind of traffic
  bench/generators/<name>.py       the data generator a configuration names
  bench/references/<name>.py       the plain reference it names
  bench/metrics/<metric>.py        the reader of each metric (or of the
                                   name it was split from)
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]   # metric entries this cell reports, trace 0
    per_layer: List[dict]    # metric entries this cell reports, trace 1


def _reported(metrics: List[dict], cell: str, moved: set) -> List[dict]:
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif not moved or m.get("moves") in moved:
            out.append(m)
    return out


def load_cell(workload: str, benchmark: Optional[dict] = None,
              traffic_dir: Optional[str] = None) -> Cell:
    """The cell ``workload`` of ``benchmark`` (default: BENCHMARK.json)."""
    bm = benchmark or _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = _json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _json(os.path.join(traffic_dir or os.path.join(BENCH, "traffic"),
                                 w["traffic"] + ".json"))
    e2e = _reported(bm["end_to_end"], workload, set())
    per_layer = _reported(bm["per_layer"], workload,
                          {m["name"] for m in e2e})
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer)


def module(kind: str, name: str):
    """``bench.<kind>.<name>`` for plain names."""
    return importlib.import_module(f"bench.{kind}.{name}")


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``.

    A metric split by the end-to-end metric it moves, one name per kind of
    cell (``dispatch_ms.online``, ``dispatch_ms.offline``), shares one
    reader: where ``<metric>.py`` is missing, the name's last dotted part
    is dropped until a file is found. Names may hold dots, so the file is
    loaded by its path."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        name = ".".join(parts[:n])
        path = os.path.join(BENCH, "metrics", name + ".py")
        if os.path.exists(path):
            break
    else:
        raise FileNotFoundError(f"no reader bench/metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """This device's peaks from ``bench/peaks.json``; unknown is an error."""
    table = _json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]
