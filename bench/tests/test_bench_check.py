"""The answer check at a size a test run holds: a narrow ResNet9 on the
CPU (``data/tiny-w2a2.json``, XLA kernels). The program passes, the
control (the reference with its float steps in bfloat16) fails, and a run
whose timed path is broken underneath comes out not correct."""

import json
from pathlib import Path

import pytest

from bench import control, roofline, run_cell, spec

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((Path(__file__).parent / "data" / "tiny-w2a2.json")
                 .read_text())
SEEDS = [3, 2**31 + 77, 2**40 + 9]


@pytest.fixture(autouse=True)
def _own_compile_cache_settings():
    """A cell's set-up points JAX's persistent cache at the checkout and
    caches every program; give later tests of this process theirs back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _plan():
    return {"name": "tiny.saturate", "config": CFG,
            "model": spec.model(ROOT, CFG["model"]),
            "traffic": {"loop": "closed", "outstanding": 16},
            "end_to_end": [{"name": "images_per_s", "unit": "images/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def test_program_passes_and_control_fails_on_three_seeds():
    limits = CFG["check_limits"]
    for seed in SEEDS:
        r = control.readings(_plan(), seed, 0.3)
        assert r["failed"] == 0 and r["checked"] == r["answered"] > 0
        assert run_cell.verdict(r["program"], limits)[0], r
        assert not run_cell.verdict(r["control"], limits)[0], r


def _run(seed, plan=None):
    import jax
    return run_cell.run(plan or _plan(), seed, 0.3, False,
                        device=jax.devices()[0],
                        peaks=roofline.peaks(ROOT, "TPU v5 lite"))


def _alter_one_answer(st, p, x, backend, interpret):
    """The classifier with the first answer of every batch altered."""
    y = x @ p["w"].astype(x.dtype)
    return y.at[0, 0].add(0.5 * abs(y[0]).max())


def _misroute_answers(st, p, x, backend, interpret):
    """The classifier answering each image with its neighbour's logits."""
    return (x @ p["w"].astype(x.dtype))[::-1]


@pytest.mark.parametrize("fault", [_alter_one_answer, _misroute_answers])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.compiler import executor
    res = _run(SEEDS[0])
    assert res["correct"] and res["failed"] == 0
    monkeypatch.setitem(executor._APPLY, "host_gemm", fault)
    res = _run(SEEDS[0])
    assert res["failed"] == 0
    assert not res["correct"], res["check"]
    assert res["check"]["gap_max"]["value"] > CFG["check_limits"]["gap_max"]


def test_new_model_is_files_only(tmp_path):
    """A model unlike ResNet9 (a 1x1 conv, an unpadded stride-2 conv),
    added as a model module, a configuration and entries alone: its cell
    resolves, runs correct on the CPU, and the trace matcher finds its 1x1
    conv."""
    import shutil
    from bench import kernels
    data = Path(__file__).parent / "data"
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(data / "toy_model.py", tmp_path / "bench" / "models"
                / "toy.py")
    shutil.copy(data / "toy.json", tmp_path / "bench" / "configs"
                / "toy.json")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "toy", "source": "x", "reduced": [],
                          "file": "bench/configs/toy.json", "why": "x"})
    bm["workloads"].append({"name": "toy.saturate", "config": "toy",
                            "traffic": "saturate", "chips": 1, "why": "x"})
    for m in bm["end_to_end"]:
        m.get("workloads", []).append("toy.saturate")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    plan = spec.cell(tmp_path, "toy.saturate")
    convs = plan["model"].convs(plan["config"])
    assert [(l["name"], l["fh"], l["padding"], l["ho"]) for l in convs] \
        == [("pw", 1, 0, 10), ("down", 3, 0, 4)]
    res = _run(SEEDS[1], plan)
    assert res["correct"] and res["failed"] == 0, res["check"]
    assert res["checked"] > 0 and res["metrics"]["images_per_s"]["value"]

    pw = ("%run.3 = u32[2,1,8,10,10,1]{5,4,3,2,1,0} custom-call(%p, %w), "
          "custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={u32[2,8,10,10,1]{4,3,2,1,0}, "
          "u32[1,2,1,1,1,32]{5,4,3,2,1,0}, f32[1,1,32]{2,1,0}}")
    calls = kernels.conv_calls([("/device:TPU:0", pw, 0.0, 1e6)], convs,
                               plan["config"])
    assert [(l["name"], n) for l, n, _ in calls] == [("pw", 8)]
    resnet9 = spec.model(ROOT, "resnet9")
    assert kernels.conv_calls([("/device:TPU:0", pw, 0.0, 1e6)],
                              resnet9.convs(CFG), CFG) == []
