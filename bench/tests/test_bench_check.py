"""The answer check at a size a test run holds: a narrow ResNet9 on the
CPU (``data/tiny-w2a2.json``, XLA kernels). The program passes, the
control (the reference with its float steps in bfloat16) fails, and a run
whose timed path is broken underneath comes out not correct."""

import json
from pathlib import Path

import pytest

from bench import control, roofline, run_cell

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


def _run(seed):
    import jax
    return run_cell.run(_plan(), seed, 0.3, False, device=jax.devices()[0],
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
