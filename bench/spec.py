"""Everything a cell needs, found by name from ``BENCHMARK.json``.

A workload entry names its configuration (``bench/configs/<file>``, as
the configuration entry says), its traffic mix
(``bench/traffic/<traffic>.json``) and, through the metric entries that
apply to it, one reader per per-layer metric
(``bench/metrics/<metric>.py``, whose ``read(ctx)`` returns a number or
``None``). Adding a cell, a mix or a metric adds files and entries; no
code here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from bench import traffic


class SpecError(ValueError):
    pass


def load_benchmark(root: Path) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(root: Path, name: str) -> dict:
    """The workload ``name`` with its configuration, traffic mix and the
    end-to-end and per-layer metrics it reports."""
    root = Path(root)
    bm = load_benchmark(root)
    wl = {w["name"]: w for w in bm["workloads"]}.get(name)
    if wl is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in bm["configs"]}.get(wl["config"])
    if cfg_entry is None:
        raise SpecError(f"workload {name}: no configuration "
                        f"{wl['config']!r}")
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    return {
        "name": name,
        "workload": wl,
        "config": cfg,
        "traffic": traffic.load(root, wl["traffic"]),
        "end_to_end": [m for m in bm["end_to_end"]
                       if _applies(m, name)],
        "per_layer": [m for m in bm["per_layer"] if _applies(m, name)],
    }


def reader(root: Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
