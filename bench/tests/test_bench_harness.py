"""The cell command's refusals, and cells, mixes and metrics found by
name from data files alone."""

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]


def test_cpu_run_exits_nonzero_with_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run_cell.py"), "--workload",
         "resnet9-w2a2.saturate", "--seed", str(2**33 + 5), "--seconds",
         "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(l.strip().startswith("{") for l in r.stdout.splitlines())


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError):
        spec.cell(ROOT, "no-such-cell")


def test_every_cell_resolves_with_readers():
    bm = spec.load_benchmark(ROOT)
    for wl in bm["workloads"]:
        plan = spec.cell(ROOT, wl["name"])
        assert plan["config"]["layers"]
        assert all(callable(getattr(plan["model"], f))
                   for f in spec.MODEL_FUNCTIONS)
        assert any(m["name"] == "setup_s" for m in plan["end_to_end"])
        assert len(plan["end_to_end"]) >= 2 and plan["per_layer"]
        for m in plan["per_layer"]:
            assert callable(spec.reader(ROOT, m["name"]))


def test_new_cell_and_metric_are_files_only(tmp_path):
    """A cell, a mix, a configuration and a per-layer metric added as
    files and entries are found without a code change."""
    bench = _copy_bench(tmp_path)
    cfg = json.loads((bench / "configs" / "resnet9-w4a8.json").read_text())
    cfg["max_batch"] = 64
    (bench / "configs" / "resnet9-w4a8-b64.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "trickle.json").write_text(json.dumps(
        {"loop": "open", "images_per_s": 50.0}))
    (bench / "metrics" / "span_count.trickle.py").write_text(
        "def read(ctx):\n    return float(len(ctx.spans)) or None\n")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "resnet9-w4a8-b64", "source": "x",
                          "file": "bench/configs/resnet9-w4a8-b64.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "resnet9-w4a8-b64.trickle",
                            "config": "resnet9-w4a8-b64",
                            "traffic": "trickle", "chips": 1, "why": "x"})
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("resnet9-w4a8-b64.saturate")
    bm["workloads"].append({"name": "resnet9-w4a8-b64.saturate",
                            "config": "resnet9-w4a8-b64",
                            "traffic": "saturate", "chips": 1, "why": "x"})
    bm["end_to_end"].append({"name": "latency_p95_ms", "unit": "ms",
                             "better": "lower", "bound": 0.25,
                             "source": "host_clock",
                             "workloads": ["resnet9-w4a8-b64.trickle"]})
    bm["per_layer"].append({"name": "span_count.trickle", "unit": "spans",
                            "better": "higher", "source": "program_span",
                            "layer": "serving", "moves": "latency_p95_ms",
                            "workloads": ["resnet9-w4a8-b64.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    plan = spec.cell(tmp_path, "resnet9-w4a8-b64.trickle")
    assert plan["config"]["max_batch"] == 64
    assert plan["traffic"]["images_per_s"] == 50.0
    assert [m["name"] for m in plan["per_layer"]] == ["span_count.trickle"]
    assert {m["name"] for m in plan["end_to_end"]} == {"latency_p95_ms",
                                                      "setup_s"}
    sat = spec.cell(tmp_path, "resnet9-w4a8-b64.saturate")
    assert {m["name"] for m in sat["end_to_end"]} == {"images_per_s",
                                                     "setup_s"}
    read = spec.reader(tmp_path, "span_count.trickle")
    assert read(types.SimpleNamespace(spans=[1, 2, 3])) == 3.0
    assert read(types.SimpleNamespace(spans=[])) is None


def test_compile_count_sees_programs_built_in_the_window():
    import jax.numpy as jnp
    from bench.run_cell import CompileCount
    jnp.ones((3, 5)).sum()
    with CompileCount() as warm:
        jnp.ones((3, 5)).sum()
    with CompileCount() as cold:
        jnp.ones((3, 6)).sum()
    assert warm.n == 0 and cold.n >= 1


def _copy_bench(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path / "bench"


@pytest.mark.parametrize("model, module", [
    ("no-such-model", None),
    ("half", "def make_params(cfg, seed):\n    pass\n"),
    ("../models/resnet9", None),
])
def test_unknown_model_is_refused_before_jax(tmp_path, model, module):
    """A configuration whose model module is missing or lacks a required
    function is a SpecError, and the cell command exits 2 without
    importing JAX."""
    bench = _copy_bench(tmp_path)
    if module is not None:
        (bench / "models" / f"{model}.py").write_text(module)
    cfg_path = bench / "configs" / "resnet9-w2a2.json"
    cfg_path.write_text(json.dumps(dict(json.loads(cfg_path.read_text()),
                                        model=model)))
    with pytest.raises(spec.SpecError):
        spec.cell(tmp_path, "resnet9-w2a2.saturate")
    code = ("import runpy, sys\n"
            f"sys.argv = ['run_cell.py', '--workload', "
            "'resnet9-w2a2.saturate', '--seed', '1', '--seconds', '1']\n"
            "try:\n"
            f"    runpy.run_path({str(bench / 'run_cell.py')!r}, "
            "run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    print(e.code, 'jax' in sys.modules)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.stdout.split() == ["2", "False"], (r.stdout, r.stderr)
    assert "model" in r.stderr
