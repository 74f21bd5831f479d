"""Where the benchmark finds what a cell is made of, by name.

`BENCHMARK.json` at the checkout's root names every cell (a
configuration under a traffic mix) and every metric.  Everything that
belongs to one configuration, one traffic mix or one per-layer metric
sits in a file of its own under `bench/`, found by the name alone:

    bench/configs/<config>.json     sizes, load arguments, source
    bench/models/<model_type>.py    the plain float32 reference
    bench/traffic/<traffic>.json    parameters of the one generator
    bench/metrics/<metric>.py       a reader: read(run) -> number | None
    bench/checks/<cell>.json        the limits `correct` is judged by

So a later change adds a cell, a configuration, a mix or a metric by
adding files and entries, and edits none of these."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a Python file by path (metric and model names hold dots and
    dashes, which `import` cannot spell)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of BENCHMARK.json with its files resolved."""

    name: str
    chips: int
    config_name: str
    config: dict            # bench/configs/<config>.json
    traffic_name: str
    traffic: dict           # bench/traffic/<traffic>.json
    check: dict             # bench/checks/<cell>.json
    end_to_end: List[dict]  # contract entries this cell reports
    per_layer: List[dict]

    def model_module(self):
        mt = self.config["model_type"]
        return load_module(os.path.join(BENCH, "models", f"{mt}.py"),
                           f"bench_model_{mt.replace('-', '_')}")


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` and every file it needs; KeyError if unknown."""
    bm = benchmark(root)
    by_name = {w["name"]: w for w in bm["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    bench = os.path.join(root, "bench")
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=_load_json(os.path.join(bench, "configs",
                                       f"{w['config']}.json")),
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(bench, "traffic",
                                        f"{w['traffic']}.json")),
        check=_load_json(os.path.join(bench, "checks", f"{name}.json")),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """`read(run)` of bench/metrics/<name>.py."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    mod = load_module(path, "bench_metric_" + name.replace(".", "_")
                      .replace("-", "_"))
    return mod.read


def read_metrics(metrics: List[dict], run, root: str = ROOT
                 ) -> Dict[str, dict]:
    """{name: {"value", "unit"}} for every metric whose reader found
    something; a reader that returns None leaves its metric out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        v: Optional[float] = metric_reader(m["name"], root)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
