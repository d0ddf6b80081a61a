"""The benchmark's data files, found by name.

``BENCHMARK.json`` (the checkout root) names every cell; each cell names a
configuration and a traffic mix, and each per-layer metric has a reader of
its own.  Everything that belongs to one of them is a file of its own:

* ``bench/configs/<config>.json``   the model as it is run;
* ``bench/traffic/<traffic>.json``  the training job (batch, sequence,
  nodes, topology, algorithm, learning rate);
* ``bench/limits/<workload>.json``  the limits of the correctness check;
* ``bench/metrics/<metric>.py``     the reader of one metric;
* ``bench/peaks.json``              the chips' published peaks.

A later cell, configuration or metric is a new file; no file here needs an
edit for it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: str | None
    reader: object  # the module bench/metrics/<name>.py


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, metrics_dir: Path = BENCH / "metrics"):
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, *, benchmark: Path = ROOT / "BENCHMARK.json",
              files: Path = BENCH) -> Cell:
    """The cell ``workload`` of ``benchmark`` with its files under ``files``
    (``configs/``, ``traffic/``, ``limits/``); readers from ``bench/metrics``."""
    bm = _json(benchmark)
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"one of {sorted(cells)}")
    w = cells[workload]

    def metrics(entries):
        out = []
        for m in entries:
            listed = m.get("workloads")
            if listed is not None and workload not in listed:
                continue
            reader = load_reader(m["name"])
            if reader.UNIT != m["unit"]:
                raise ValueError(f"{m['name']}: the reader's unit {reader.UNIT!r} "
                                 f"is not {m['unit']!r}")
            out.append(Metric(name=m["name"], unit=m["unit"],
                              moves=m.get("moves"), reader=reader))
        return tuple(out)

    e2e = metrics(bm["end_to_end"])
    names = {m.name for m in e2e}
    per_layer = tuple(m for m in metrics(bm["per_layer"])
                      if m.moves in names)
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=_json(files / "configs" / f"{w['config']}.json"),
        traffic=_json(files / "traffic" / f"{w['traffic']}.json"),
        limits=_json(files / "limits" / f"{workload}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def peaks_for(device_kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """The published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = _json(path)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path.name}: "
                       f"one of {sorted(table)}")
    return table[device_kind]
