"""The chip smoke script's refusals and its logit comparison: it never
prints a passing result line for a run in which no kernel ran on a TPU,
and it accepts a difference in the logits only where a known cause is
named."""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import repro.runtime.compile_cache as compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_devices(monkeypatch, platform, kind):
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")


def _result_lines(out: str):
    return [ln for ln in out.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("platform,kind,argv", [
    ("tpu", "TPU v5 lite", ["--interpret"]),   # interpret mode on a chip
    ("gpu", "some GPU", ["--interpret"]),
    ("cpu", "cpu", []),                         # no chip, no --interpret
])
def test_refuses_runs_that_could_pass_for_a_chip_run(
        smoke, monkeypatch, capsys, platform, kind, argv):
    _fake_devices(monkeypatch, platform, kind)
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert "error:" in out.err
    assert not _result_lines(out.out)
    assert not any('"ok"' in ln for ln in out.out.splitlines())


def test_compare_identical_passes(smoke, capsys):
    x = np.arange(20, dtype=np.float32).reshape(2, 10)
    smoke._compare("t", x, x.copy())
    assert "identical" in capsys.readouterr().out


def test_compare_any_difference_fails_without_a_cause(smoke):
    want = np.linspace(-1, 1, 20, dtype=np.float32).reshape(2, 10)
    got = np.nextafter(want, np.float32(np.inf))
    with pytest.raises(AssertionError, match="disagree"):
        smoke._compare("t", got, want)


def test_compare_named_cause_allows_only_the_ulp_bound(smoke, capsys):
    want = np.linspace(-1, 1, 20, dtype=np.float32).reshape(2, 10)
    got = np.nextafter(want, np.float32(np.inf))
    smoke._compare("t", got, want, ulp_cause=smoke.CPU_ONE_ROW_DOT)
    assert "known cause" in capsys.readouterr().out
    far = want + np.float32(1e-3)
    with pytest.raises(AssertionError, match="disagree"):
        smoke._compare("t", far, want, ulp_cause=smoke.CPU_ONE_ROW_DOT)
    flipped = want.copy()
    flipped[0, [0, -1]] = flipped[0, [-1, 0]]
    with pytest.raises(AssertionError, match="disagree"):
        smoke._compare("t", flipped, want, ulp_cause=smoke.CPU_ONE_ROW_DOT)

