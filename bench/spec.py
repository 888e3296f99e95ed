"""Everything a cell needs, found by name from ``BENCHMARK.json``.

A workload entry names its configuration (``bench/configs/<file>``, as
the configuration entry says), its traffic mix
(``bench/traffic/<traffic>.json``) and, through the metric entries that
apply to it, one reader per per-layer metric
(``bench/metrics/<metric>.py``, whose ``read(ctx)`` returns a number or
``None``). The configuration's ``"model"`` key names its model module,
``bench/models/<model>.py``, which defines every function of
:data:`MODEL_FUNCTIONS`. Adding a cell, a mix, a metric or a model adds
files and entries; no code here changes.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import re
from pathlib import Path

from bench import traffic


#: what a model module defines: ``make_params(cfg, seed)`` the seeded
#: float weights; ``graph(params, cfg)`` the compiler graph the server
#: takes; ``logits(params, calib, images, cfg, *, dtype, block)`` the
#: plain reference (the control at ``dtype=bfloat16``); ``convs(cfg)``
#: every conv the Program runs, as ``bench/roofline.py`` counts it;
#: ``macs_per_image(cfg)`` the served graph's multiply-accumulates
MODEL_FUNCTIONS = ("make_params", "graph", "logits", "convs",
                   "macs_per_image")


_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


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
        "model": model(root, cfg.get("model")),
        "traffic": traffic.load(root, wl["traffic"]),
        "end_to_end": [m for m in bm["end_to_end"]
                       if _applies(m, name)],
        "per_layer": [m for m in bm["per_layer"] if _applies(m, name)],
    }


def _load(path: Path, prefix: str, name: str):
    mod_spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def model(root: Path, name) -> object:
    """The model module ``bench/models/<name>.py``. Its functions are
    checked from its source before it is imported, so a missing or
    incomplete module is refused without loading JAX."""
    if not (isinstance(name, str) and _NAME.fullmatch(name)):
        raise SpecError(f"bad model name {name!r}")
    path = Path(root) / "bench" / "models" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no model module {path} for model {name!r}")
    try:
        tree = ast.parse(path.read_text(), str(path))
    except SyntaxError as e:
        raise SpecError(f"model module {path}: {e}") from None
    defined = {n.name for n in tree.body if isinstance(n, ast.FunctionDef)}
    missing = [f for f in MODEL_FUNCTIONS if f not in defined]
    if missing:
        raise SpecError(f"model module {path} lacks {', '.join(missing)}")
    return _load(path, "bench_model_", name)


def reader(root: Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path}")
    return _load(path, "bench_metric_", metric).read
