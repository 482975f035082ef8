"""Finds a cell's files by name.

``BENCHMARK.json`` (at the checkout's root) lists the cells, the
configurations and the metrics.  Everything that belongs to one of them
sits in a file of its own under ``bench/``, found by its name:

* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/workloads/<cell>.json``: the cell's traffic, its job and the
  limits of its ``correct`` comparison;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric, a
  ``read(rec)`` that returns a number, or None where the traced run has
  nothing for it to read;
* ``bench/benchlib/families/<family>.py``: the model family named by the
  configuration's ``family`` key (``classifier`` where there is none).
  It gives
  - ``make_traffic(wl, seed) -> traffic.Traffic``: the cell's data;
  - ``program_model(cfg, wl) -> (init, apply)``: the program's model,
    the one function that imports the program, inside its body;
  - ``init_params(cfg, key)`` and ``loss(cfg, params, x, y, mask)``, the
    masked mean over real rows (or tokens): the plain reference;
  - ``head_update(cfg, new, old) -> (K, C)``: the head update HiCS-FL
    reads, from K clients' parameters (leading axis) and the global
    ones; C is the configuration's ``num_classes``;
  - ``train_flops_per_row(cfg, wl)``: forward and backward of one row.

So a later change adds a cell, a configuration, a metric or a model
family by adding files and entries, and edits none that is there.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict            # the cell's entry in BENCHMARK.json
    workload: dict         # bench/workloads/<name>.json
    config: dict           # bench/configs/<config>.json
    end_to_end: List[dict]  # metrics the cell reports untraced
    per_layer: List[dict]   # metrics the cell reports traced


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT,
         bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``name`` with its workload, configuration and metrics."""
    bench_dir = bench_dir or root / "bench"
    spec = benchmark(root)
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        known = sorted(w["name"] for w in spec["workloads"])
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    entry = entries[0]
    workload = load_json(bench_dir / "workloads" / f"{name}.json")
    config = load_json(bench_dir / "configs" / f"{entry['config']}.json")
    return Cell(
        name=name, entry=entry, workload=workload, config=config,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _load(bench_dir / "metrics" / f"{metric}.py",
                 "bench_metric_" + metric).read


def family(config: dict, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The module of the configuration's model family."""
    name = config.get("family", "classifier")
    return _family(bench_dir / "benchlib" / "families" / f"{name}.py")


@functools.lru_cache(maxsize=None)
def _family(path: Path) -> ModuleType:
    """Loaded once a process: a family's jitted functions keep their
    compiled programs across the jobs of a run."""
    return _load(path, "bench_family_" + path.stem)
