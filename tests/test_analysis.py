"""Mutation-style tests for the static verification suite.

Each test seeds one defect class into a known-good artifact (IR graph,
lowered Program, command stream, or source file) and asserts the verifier
rejects it with the right check id and blame. A clean sweep over the
canonical workloads (bench graph, ResNet9, two LM decode streams) pins
the false-positive rate at zero, and the off-path test counter-proves
that verification does exactly no work when ``REPRO_VERIFY`` is unset.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import analysis
from repro.analysis.lint import Finding, lint_file, run_lint
from repro.analysis.verify_ir import (VerifyError, verify_graph,
                                      verify_program)
from repro.analysis.verify_stream import StreamError, verify_stream
from repro.compiler import passes
from repro.compiler.artifact import (ArtifactError, ArtifactStore,
                                     load_program, save_program)
from repro.compiler.bench_graphs import tiny_mixed_cnn
from repro.compiler.ir import Graph, Node
from repro.compiler.lower import compile_graph
from repro.configs import get_arch
from repro.core.codegen import CommandStream
from repro.core.mvu import MVU_COUNT, MVUJob, OpKind
from repro.models.layers import QuantPolicy
from repro.runtime.controller import BarrelController
from repro.serving.lm_engine import decode_cost_stream

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _policy():
    return QuantPolicy(mode="serial", w_bits=2, a_bits=2, radix_bits=7)


def _annotated():
    """tiny_mixed_cnn after the full pass pipeline (precision-annotated)."""
    g, _ = tiny_mixed_cnn()
    pol = _policy()
    passes.run_pipeline(g, pol)
    return g, pol


def _gemm_graph(seed=0):
    rng = np.random.RandomState(seed)
    g = Graph("gemm_only", {"x": (None, 16)}, ["y"],
              [Node("fc", "gemm", ["x", "fc.w"], "y")],
              {"fc.w": (rng.randn(16, 8) * 0.2).astype(np.float32)})
    return g, rng.rand(4, 16).astype(np.float32)


@pytest.fixture(scope="module")
def tiny_prog():
    g, calib = tiny_mixed_cnn()
    return compile_graph(g, calib)


@pytest.fixture(scope="module")
def tiny_stream(tiny_prog):
    return tiny_prog.to_command_stream()


# ==========================================================================
# graph verifier: seeded defects
# ==========================================================================

def test_clean_graph_verifies():
    g, pol = _annotated()
    shapes = verify_graph(g, policy=pol)
    assert "y" in shapes


def test_defect_dangling_output():
    g, pol = _annotated()
    g.outputs = ["ghost"]
    with pytest.raises(VerifyError) as ei:
        verify_graph(g, policy=pol, blame="mutation")
    assert ei.value.check in ("graph-structure", "dangling-output")
    assert ei.value.blame == "mutation"


def test_defect_dangling_node_input():
    g, pol = _annotated()
    g.nodes.append(Node("evil", "relu", ["phantom"], "evil.y"))
    g.outputs = ["evil.y"]
    with pytest.raises(VerifyError) as ei:
        verify_graph(g, policy=pol)
    assert ei.value.check == "graph-structure"


def test_defect_shape_annotation_lie():
    g, pol = _annotated()
    g.nodes[0].attrs["shape"] = (1, 2, 3)
    with pytest.raises(VerifyError) as ei:
        verify_graph(g, policy=pol, blame="annotator")
    assert ei.value.check == "shape-annotation"
    assert ei.value.blame == "annotator"


def test_defect_shape_drift():
    g, pol = _annotated()
    with pytest.raises(VerifyError) as ei:
        verify_graph(g, policy=pol,
                     expect_output_shapes={"y": (None, 999)})
    assert ei.value.check == "shape-drift"


def test_defect_precision_out_of_range():
    g, pol = _annotated()
    victim = next(n for n in g.nodes
                  if n.attrs.get("precision", {}).get("mode") == "serial")
    victim.attrs["precision"]["a_bits"] = 12
    with pytest.raises(VerifyError) as ei:
        verify_graph(g, policy=pol)
    assert ei.value.check == "precision-range"


def test_defect_precision_policy_mismatch():
    g, pol = _annotated()
    victim = next(n for n in g.nodes
                  if n.attrs.get("precision", {}).get("mode") == "serial")
    victim.attrs["precision"]["a_bits"] = 3  # valid range, wrong policy
    with pytest.raises(VerifyError) as ei:
        verify_graph(g, policy=pol)
    assert ei.value.check == "precision-policy"
    assert victim.name in str(ei.value)


def test_pass_sandwich_blames_the_corrupting_pass(monkeypatch):
    """A pass that corrupts the graph is caught by the very next sandwich
    check, with the pass's own name as blame."""
    def evil(g):
        g.nodes[0].attrs["shape"] = (6, 6, 6)
        return g
    monkeypatch.setattr(passes, "fuse_epilogues", evil)
    analysis.reset_counters()
    g, _ = tiny_mixed_cnn()
    with pytest.raises(VerifyError) as ei:
        passes.run_pipeline(g, _policy())
    assert ei.value.check == "shape-annotation"
    assert ei.value.blame == "fuse_epilogues"
    # the sandwich ran for the passes before the corrupting one too
    assert analysis.counters()["pass_sandwich"] >= 1


# ==========================================================================
# program verifier: seeded defects
# ==========================================================================

def _with_steps(prog, steps):
    return dataclasses.replace(prog, steps=tuple(steps),
                               _jit_cache={})


def test_defect_step_unknown_kind(tiny_prog):
    steps = list(tiny_prog.steps)
    steps[0] = dataclasses.replace(steps[0], kind="warp_drive")
    with pytest.raises(VerifyError) as ei:
        verify_program(_with_steps(tiny_prog, steps))
    assert ei.value.check == "step-kind"
    assert ei.value.blame == steps[0].name


def test_defect_step_dangling_input(tiny_prog):
    steps = list(tiny_prog.steps)
    steps[1] = dataclasses.replace(steps[1], inputs=("ghost",))
    with pytest.raises(VerifyError) as ei:
        verify_program(_with_steps(tiny_prog, steps))
    assert ei.value.check == "step-dangling-input"
    assert ei.value.blame == steps[1].name


def test_defect_step_redefinition(tiny_prog):
    steps = list(tiny_prog.steps)
    steps[1] = dataclasses.replace(steps[1], output=steps[0].output)
    with pytest.raises(VerifyError) as ei:
        verify_program(_with_steps(tiny_prog, steps))
    assert ei.value.check == "step-redefinition"


def test_defect_program_output_unproduced(tiny_prog):
    bad = dataclasses.replace(tiny_prog, output_name="ghost",
                              _jit_cache={})
    with pytest.raises(VerifyError) as ei:
        verify_program(bad)
    assert ei.value.check == "program-output"


def test_defect_missing_step_params(tiny_prog):
    victim = tiny_prog.steps[-1].name
    params = {k: v for k, v in tiny_prog.params.items() if k != victim}
    bad = dataclasses.replace(tiny_prog, params=params, _jit_cache={})
    with pytest.raises(VerifyError) as ei:
        verify_program(bad)
    assert ei.value.check == "step-params"
    assert ei.value.blame == victim


def test_defect_per_layer_bits_vs_spec(tiny_prog):
    packed = next(s for s in tiny_prog.steps
                  if s.kind in ("conv_packed", "gemm_packed"))
    bits = dict(tiny_prog.per_layer_bits)
    bits[packed.name] = (5, 5)  # in range, but not what was planned
    bad = dataclasses.replace(tiny_prog, per_layer_bits=bits,
                              _jit_cache={})
    with pytest.raises(VerifyError) as ei:
        verify_program(bad)
    assert ei.value.check == "precision-spec"
    assert ei.value.blame == packed.name


def test_defect_tile_over_vmem_budget(tiny_prog):
    steps = list(tiny_prog.steps)
    idx, victim = next((i, s) for i, s in enumerate(steps)
                       if s.kind == "conv_packed")
    attrs = dict(victim.attrs)
    tile = dict(attrs["tile"])
    tile.update(block_nb=1 << 16, block_co=1 << 16,
                cache_weights=True, cache_acts=True)
    attrs["tile"] = tile
    steps[idx] = dataclasses.replace(victim, attrs=attrs)
    with pytest.raises(VerifyError) as ei:
        verify_program(_with_steps(tiny_prog, steps))
    assert ei.value.check == "tile-vmem"
    assert ei.value.blame == victim.name


def test_defect_tile_breaks_tiling_rule(tiny_prog):
    steps = list(tiny_prog.steps)
    idx, victim = next((i, s) for i, s in enumerate(steps)
                       if s.kind == "conv_packed")
    attrs = dict(victim.attrs)
    attrs["tile"] = dict(attrs["tile"], block_co=48)  # not a packing word
    steps[idx] = dataclasses.replace(victim, attrs=attrs)
    with pytest.raises(VerifyError) as ei:
        verify_program(_with_steps(tiny_prog, steps))
    assert ei.value.check == "tile-rule"
    assert ei.value.blame == victim.name


# ==========================================================================
# stream analyzer: seeded defects
# ==========================================================================

def _mutated(stream, i, **kw):
    jobs = list(stream.jobs)
    jobs[i] = dataclasses.replace(jobs[i], **kw)
    return CommandStream(jobs=jobs, mode=stream.mode)


def _check(stream, check, **verify_kw):
    with pytest.raises(StreamError) as ei:
        verify_stream(stream, **verify_kw)
    assert ei.value.check == check
    return ei.value


def test_defect_forward_hazard_edge(tiny_stream):
    _check(_mutated(tiny_stream, 0, depends_on=(2,)), "hazard-order",
           reconcile=False)


def test_defect_duplicate_tag(tiny_stream):
    tagged = [i for i, j in enumerate(tiny_stream.jobs) if j.tag]
    assert len(tagged) >= 2
    bad = _mutated(tiny_stream, tagged[1],
                   tag=tiny_stream.jobs[tagged[0]].tag)
    _check(bad, "tag-duplicate", reconcile=False)


def test_defect_host_job_on_mvu(tiny_stream):
    jobs = list(tiny_stream.jobs) + [
        MVUJob(op=OpKind.HOST, mvu=3, tag="host_leak")]
    _check(CommandStream(jobs=jobs, mode=tiny_stream.mode),
           "host-on-mvu", reconcile=False)


def test_defect_mvu_out_of_range(tiny_stream):
    _check(_mutated(tiny_stream, 0, mvu=MVU_COUNT + 41), "mvu-range",
           reconcile=False)


def test_xfer_implicit_destination_is_legal():
    # dest_mvu=None means self/next-stage (MVUJob's documented default):
    # hand-built streams (tests, engines) rely on it
    jobs = [MVUJob(op=OpKind.GEMV, mvu=0, tag="g0"),
            MVUJob(op=OpKind.XFER, mvu=0, tag="x0", depends_on=(0,))]
    verify_stream(CommandStream(jobs=jobs, mode="pipelined"),
                  reconcile=False)


def test_defect_xfer_to_self():
    jobs = [MVUJob(op=OpKind.XFER, mvu=2, dest_mvu=2, tag="x0")]
    _check(CommandStream(jobs=jobs, mode="pipelined"), "xfer-self",
           reconcile=False)


def test_defect_stream_precision_range(tiny_stream):
    compute = next(i for i, j in enumerate(tiny_stream.jobs)
                   if j.op not in (OpKind.XFER, OpKind.HOST))
    _check(_mutated(tiny_stream, compute, a_bits=11), "precision-range",
           reconcile=False)


def test_defect_zero_size_job(tiny_stream):
    compute = next(i for i, j in enumerate(tiny_stream.jobs)
                   if j.op not in (OpKind.XFER, OpKind.HOST))
    _check(_mutated(tiny_stream, compute, m_tiles=0), "zero-size-job",
           reconcile=False)


def test_defect_cycle_accounting_mismatch(tiny_stream):
    """A controller that books cycles the jobs never declared is caught
    by the reconciliation pass."""
    class Lying:
        def __init__(self):
            self._real = BarrelController()
            self.harts = self._real.harts

        def simulate(self, stream, xfer, **kw):
            rep = self._real.simulate(stream, xfer, **kw)
            busy = list(rep.per_mvu_busy)
            busy[0] += 7
            return dataclasses.replace(rep, per_mvu_busy=busy)

    _check(tiny_stream, "cycle-accounting", controller=Lying())


def test_stream_verify_method_and_report(tiny_stream):
    rep = tiny_stream.verify()
    assert rep is not None and rep.makespan_cycles > 0


# ==========================================================================
# property tests (hypothesis; deterministic stub on bare interpreters)
# ==========================================================================

@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=-4, max_value=64))
def test_prop_precision_outside_serial_range_rejected(bits):
    job = MVUJob(op=OpKind.GEMV, mvu=0, a_bits=bits, tag="g0")
    cs = CommandStream(jobs=[job], mode="pipelined")
    if 1 <= bits <= 8:
        verify_stream(cs, reconcile=False)
    else:
        with pytest.raises(StreamError) as ei:
            verify_stream(cs, reconcile=False)
        assert ei.value.check == "precision-range"


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=-2, max_value=40))
def test_prop_dependency_edges_must_point_backwards(dep):
    jobs = [MVUJob(op=OpKind.GEMV, mvu=0, tag="a"),
            MVUJob(op=OpKind.GEMV, mvu=0, tag="b", depends_on=(dep,))]
    cs = CommandStream(jobs=jobs, mode="pipelined")
    if dep == 0:
        verify_stream(cs, reconcile=False)
    else:
        with pytest.raises(StreamError) as ei:
            verify_stream(cs, reconcile=False)
        assert ei.value.check == "hazard-order"


# ==========================================================================
# clean sweep: zero false positives on canonical workloads
# ==========================================================================

def test_clean_sweep_tiny_cnn(tiny_prog):
    verify_program(tiny_prog)
    for mode in ("pipelined", "distributed"):
        verify_stream(tiny_prog.to_command_stream(mode=mode))


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "deepseek-v2-lite-16b"])
def test_clean_sweep_lm_decode_stream(arch):
    cs = decode_cost_stream(get_arch(arch).smoke)
    assert len(cs.jobs) > 0
    rep = verify_stream(cs)
    assert rep.makespan_cycles > 0


def test_clean_sweep_resnet9():
    import jax
    import jax.numpy as jnp
    from repro.models.resnet import (ResNet9Config, resnet9_compile,
                                     resnet9_init)
    cfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(0), cfg)
    images = jnp.asarray(np.random.RandomState(0).rand(2, 16, 16, 3),
                         jnp.float32)
    # compile under REPRO_VERIFY runs the sandwich + post-lowering checks
    prog = resnet9_compile(params, images, cfg, backend="xla",
                           input_hw=16)
    verify_program(prog)
    verify_stream(prog.to_command_stream())


# ==========================================================================
# off-path: disabled verification does exactly zero work
# ==========================================================================

def test_disabled_verification_never_invoked(monkeypatch):
    monkeypatch.delenv("REPRO_VERIFY", raising=False)
    assert not analysis.verify_enabled()
    analysis.reset_counters()
    g, calib = _gemm_graph(seed=1)
    prog = compile_graph(g, calib)
    prog.to_command_stream()
    c = analysis.counters()
    assert all(c[site] == 0 for site in analysis.GATED_SITES), c


def test_enabled_verification_counts_every_site(monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "1")
    analysis.reset_counters()
    g, calib = _gemm_graph(seed=2)
    prog = compile_graph(g, calib)
    prog.to_command_stream()
    c = analysis.counters()
    assert c["pass_sandwich"] == len(passes._PIPELINE)
    assert c["post_lowering"] == 1
    assert c["to_command_stream"] == 1


# ==========================================================================
# artifact trust boundary: tampered manifests are rejected by name
# ==========================================================================

def test_artifact_tamper_rejected_by_program_verifier(tmp_path, tiny_prog):
    store = ArtifactStore(str(tmp_path / "store"))
    ref = save_program(tiny_prog, store)
    assert load_program(ref, store) is not None  # clean round trip

    # hash-consistent tamper: re-digested manifest, dangling step input.
    # Integrity hashing cannot catch this — the verifier must.
    manifest = store.get_program(ref)
    victim = manifest["steps"][1]
    victim["inputs"] = ["ghost"]
    bad_ref = store.put_program(manifest)
    assert bad_ref != ref
    with pytest.raises(ArtifactError) as ei:
        load_program(bad_ref, store)
    assert "step-dangling-input" in str(ei.value)
    assert isinstance(ei.value.__cause__, VerifyError)


def test_artifact_load_always_verifies(tmp_path, tiny_prog, monkeypatch):
    """The artifact-load check is a trust boundary: it runs even with
    REPRO_VERIFY unset."""
    monkeypatch.delenv("REPRO_VERIFY", raising=False)
    store = ArtifactStore(str(tmp_path / "store"))
    ref = save_program(tiny_prog, store)
    analysis.reset_counters()
    load_program(ref, store)
    assert analysis.counters()["artifact_load"] == 1


# ==========================================================================
# lint: unit tests on synthetic sources
# ==========================================================================

_GUARDED_SRC = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []   # guarded-by: _lock
        self._count = 0    # guarded-by: _lock

    def bad(self, x):
        self._items = [x]

    def bad_aug(self):
        self._count += 1

    def good(self, x):
        with self._lock:
            self._items = [x]

    def helper(self, x):  # requires: _lock
        self._items = [x]

    def silenced(self, x):
        self._items = [x]  # lint: disable=guarded-by
'''


def _lint_src(tmp_path, src, name="mod.py"):
    p = tmp_path / name
    p.write_text(src)
    return lint_file(str(p))


def test_lint_guarded_by(tmp_path):
    findings = _lint_src(tmp_path, _GUARDED_SRC)
    assert [f.check for f in findings] == ["guarded-by", "guarded-by"]
    assert {f.symbol for f in findings} == {"Box.bad._items",
                                            "Box.bad_aug._count"}


def test_lint_bare_assert(tmp_path):
    findings = _lint_src(tmp_path, "def f(x):\n    assert x > 0\n")
    assert [f.check for f in findings] == ["bare-assert"]


def test_lint_time_time(tmp_path):
    src = "import time\n\ndef f():\n    return time.time()\n"
    findings = _lint_src(tmp_path, src)
    assert [f.check for f in findings] == ["time-time"]


def test_lint_mutable_default(tmp_path):
    findings = _lint_src(tmp_path, "def f(x, acc=[]):\n    return acc\n")
    assert [f.check for f in findings] == ["mutable-default"]


def test_lint_syntax_error(tmp_path):
    findings = _lint_src(tmp_path, "def f(:\n")
    assert [f.check for f in findings] == ["syntax-error"]


def test_lint_baseline_grandfathers_by_symbol(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("def f(x):\n    assert x\n")
    findings, _ = run_lint([str(p)])
    assert len(findings) == 1
    baseline = {f.key() for f in findings}
    findings2, grandfathered = run_lint([str(p)], baseline)
    assert findings2 == [] and grandfathered == 1


def _cli(args, cwd=REPO):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-m", "repro.analysis"] + args,
                          capture_output=True, text=True, env=env, cwd=cwd)


def test_cli_exit_contract(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("def f(x, acc=[]):\n    return acc\n")

    r = _cli([str(clean)])
    assert r.returncode == 0 and "clean" in r.stdout
    r = _cli([str(dirty)])
    assert r.returncode == 1 and "mutable-default" in r.stdout
    r = _cli([str(tmp_path / "nope.py")])
    assert r.returncode == 2


def test_cli_shipped_tree_is_clean():
    """The acceptance gate: the lint exits 0 on the shipped tree with the
    (empty) shipped baseline."""
    r = _cli(["src"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_shipped_baseline_is_empty():
    with open(os.path.join(REPO, ".analysis-baseline.json")) as f:
        assert json.load(f) == []
