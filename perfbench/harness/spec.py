"""Finds a cell's configuration, traffic mix, limits, reference modules and
metric readers by the names ``BENCHMARK.json`` gives them, so that a new
configuration, cell, mix or metric is new files and entries, and no edit.

A configuration file names its plain reference under ``reference``: the
model module (``param_shapes``, ``logits``, ``train_flops_per_example``)
and, where its traffic is raw logs, the feature-extraction module
(``extract``), each a path from the checkout's root."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: Dict, name: str, root: str = ROOT,
         bench_dir: str = BENCH_DIR) -> Dict:
    """The workload entry ``name`` with its configuration, mix and limits
    loaded, and the metrics it reports with and without a trace."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[w["config"]]["file"]))
    return {
        "workload": w,
        "config": cfg,
        "traffic": load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        "limits": load_json(os.path.join(bench_dir, "limits", name + ".json")),
        "end_to_end": _reported(bench["end_to_end"], name),
        "per_layer": _reported(bench["per_layer"], name),
        "model": module(cfg["reference"]["model"], root),
        "fe": (module(cfg["reference"]["fe"], root)
               if "fe" in cfg["reference"] else None),
        "bench_dir": bench_dir,
    }


def _reported(metrics: List[Dict], name: str) -> List[Dict]:
    return [m for m in metrics if name in m.get("workloads", [name])]


def module(path: str, root: str = ROOT):
    """The Python file at ``path`` (from the checkout's root), loaded."""
    full = os.path.join(root, path)
    name = "perfbench_" + "".join(ch if ch.isalnum() else "_"
                                  for ch in os.path.relpath(full, root)[:-3])
    spec = importlib.util.spec_from_file_location(name, full)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(run)`` of the metric's own file, ``metrics/<name>.py``, or,
    where there is none, of the file of the name with its last dotted part
    taken off, and so on: ``mfu.stream`` reads with ``metrics/mfu.py``."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        path = os.path.join(bench_dir, "metrics", ".".join(parts[:n]) + ".py")
        if os.path.exists(path):
            return module(path, bench_dir).read
    raise FileNotFoundError(f"no reader for metric {metric!r} under "
                            f"{os.path.join(bench_dir, 'metrics')}")
