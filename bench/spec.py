"""Find what ``BENCHMARK.json`` names: cells, configurations, traffic
mixes and per-layer metric readers.

Everything is found by name, so a later change adds a configuration, a
mix or a metric as new files plus new entries, and edits none:

* configuration ``<config>``   -> the ``file`` its entry names
  (``bench/configs/<config>.json``)
* traffic mix ``<traffic>``     -> ``bench/traffic/<traffic>.json``
* per-layer metric ``<metric>`` -> ``bench/metrics/<metric>.py``, which
  defines ``read(run) -> float | None``
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str | None = None       # per-layer metrics only
    moves: str | None = None       # per-layer metrics only
    workloads: tuple[str, ...] | None = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    chips: int
    config: dict
    config_path: str
    traffic: dict
    end_to_end: tuple[Metric, ...]     # the ones this cell reports
    per_layer: tuple[Metric, ...]      # the ones this cell reports


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _metric(d: dict) -> Metric:
    wl = d.get("workloads")
    return Metric(name=d["name"], unit=d["unit"], better=d["better"],
                  source=d["source"], layer=d.get("layer"),
                  moves=d.get("moves"),
                  workloads=tuple(wl) if wl is not None else None)


def _reports(m: Metric, cell: str) -> bool:
    return m.workloads is None or cell in m.workloads


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        return json.load(f)


def load_reader(metric: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<metric>.py``.  Metric
    names may hold dots, so the file is loaded by path."""
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    cfg_file = os.path.join(root, cfg_entry["file"])
    with open(cfg_file) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, "bench")
    return Cell(
        name=name, config_name=w["config"], chips=int(w["chips"]),
        config=config, config_path=cfg_file,
        traffic=load_traffic(w["traffic"], bench_dir),
        end_to_end=tuple(m for m in map(_metric, bench["end_to_end"])
                         if _reports(m, name)),
        per_layer=tuple(m for m in map(_metric, bench["per_layer"])
                        if _reports(m, name)))
