"""AOT Program artifacts: save/load round trips, integrity rejection,
registry warm boot, eviction↔store interplay, tuning persistence.

Compiles are the expensive part, so the suite shares one populated store
(module fixture: 2 models x 2 precisions) and asserts everything else —
bit-exactness, zero-recompile warm boots, corrupted-input rejection —
against it.
"""

import json
import os

import numpy as np
import pytest

from repro.compiler import (ArtifactError, ArtifactStore, compile_graph,
                            load_program, save_program)
from repro.compiler.ir import Graph, Node
from repro.kernels import tuning
from repro.models.layers import QuantPolicy
from repro.serving import ModelRegistry

W2A2 = QuantPolicy(mode="serial", w_bits=2, a_bits=2, radix_bits=7)
W2A8 = QuantPolicy(mode="serial", w_bits=2, a_bits=8, radix_bits=7)


def _tiny_graph(name, seed=0, ci=8, co=16, h=8, w=8):
    rng = np.random.RandomState(seed)
    return Graph(
        name, {"x": (None, h, w, ci)}, ["out"],
        [Node("c1", "conv2d", ["x", "c1.w"], "c1.y",
              {"stride": 1, "padding": 1}),
         Node("r1", "relu", ["c1.y"], "c1.o"),
         Node("gap", "global_avg_pool", ["c1.o"], "p"),
         Node("fc", "gemm", ["p", "fc.w"], "out", {"host": True})],
        {"c1.w": rng.randn(3, 3, ci, co).astype(np.float32),
         "fc.w": rng.randn(co, 10).astype(np.float32)})


def _calib():
    return np.random.RandomState(1).rand(4, 8, 8, 8).astype(np.float32)


def _x(batch=2):
    return np.random.RandomState(2).rand(batch, 8, 8, 8).astype(np.float32)


def _register_all(registry):
    """2 models x 2 precisions — fresh graph objects each call (a compile
    annotates the graph in place, as a real restart never sees)."""
    calib = _calib()
    return [registry.register_graph(g.name, g, calib, p)
            for g in (_tiny_graph("m0", seed=0), _tiny_graph("m1", seed=3))
            for p in (W2A2, W2A8)]


@pytest.fixture(scope="module")
def populated(tmp_path_factory):
    """(store_root, {variant: logits}) — a store holding all 4 variants,
    written by a cold registry; plus the freshly compiled outputs."""
    root = str(tmp_path_factory.mktemp("artifacts"))
    reg = ModelRegistry(store=root)
    keys = _register_all(reg)
    outs = {str(k): np.asarray(reg.program(k)(_x())) for k in keys}
    assert reg.compiles == 4 and reg.artifact_saves == 4
    return root, outs


# ------------------------------------------------------------- round trip

def test_round_trip_bit_exact(populated):
    root, outs = populated
    store = ArtifactStore(root)
    prog = load_program("m0@W2A2", store)
    # outputs, cycle counts, and the command stream all survive the disk
    np.testing.assert_array_equal(np.asarray(prog(_x())), outs["m0@W2A2"])
    fresh = compile_graph(_tiny_graph("m0", seed=0), _calib(), policy=W2A2)
    cs_fresh = fresh.to_command_stream(mode="pipelined")
    cs_load = prog.to_command_stream(mode="pipelined")
    assert cs_load.jobs == cs_fresh.jobs
    assert cs_load.per_mvu_cycles == cs_fresh.per_mvu_cycles
    assert prog.meta.get("policy", {}).get("a_bits") == 2


def test_load_accepts_ref_or_name(populated):
    root, outs = populated
    store = ArtifactStore(root)
    ref = store.resolve("m1@W2A8")
    assert ref is not None
    by_ref = load_program(ref, store)
    by_name = load_program("m1@W2A8", store)
    x = _x()
    np.testing.assert_array_equal(np.asarray(by_ref(x)),
                                  np.asarray(by_name(x)))
    assert store.stats()["loads"] == 2


def test_packed_planes_deduped_on_disk(populated):
    """W2A2 and W2A8 of one model share every packed weight plane (weight
    precision is equal), so the second save writes no new plane blobs —
    disk mirrors the registry's _share_packed."""
    root, _ = populated
    store = ArtifactStore(root)
    p_a2 = load_program("m0@W2A2", store)
    p_a8 = load_program("m0@W2A8", store)
    packed = lambda prog: {k: rec for k, rec in prog.params.items()
                           if "w_packed" in rec}
    assert packed(p_a2), "expected at least one packed plane"
    from repro.compiler import array_digest
    for k, rec in packed(p_a2).items():
        assert array_digest(rec["w_packed"]) == array_digest(
            packed(p_a8)[k]["w_packed"])
    st = store.stats()
    assert st["blob_dedups"] == 0  # fresh session: counters are in-process
    # 4 saved variants reference more logical bytes than live on disk
    assert st["dedup_ratio"] > 1.0


# ------------------------------------------------------------- rejection

def test_unknown_ref_rejected(populated):
    root, _ = populated
    store = ArtifactStore(root)
    with pytest.raises(ArtifactError, match="neither a program ref"):
        load_program("nope@W9A9", store)


def _blob_paths(root):
    d = os.path.join(root, "blobs")
    return [os.path.join(d, n) for n in sorted(os.listdir(d))]


def _restore(path, payload):
    with open(path, "wb") as f:
        f.write(payload)


@pytest.mark.parametrize("corruption", ["garbage", "truncate", "swap"])
def test_corrupt_blobs_rejected(populated, corruption):
    root, _ = populated
    store = ArtifactStore(root)
    saved = {}
    try:
        for path in _blob_paths(root):
            with open(path, "rb") as f:
                saved[path] = f.read()
            if corruption == "garbage":
                _restore(path, b"\x00not an npy file")
            elif corruption == "truncate":
                _restore(path, saved[path][:max(1, len(saved[path]) // 2)])
            elif corruption == "swap":  # valid npy, wrong content
                import io
                a = np.load(io.BytesIO(saved[path]), allow_pickle=False)
                buf = io.BytesIO()
                np.save(buf, np.zeros_like(np.atleast_1d(a)),
                        allow_pickle=False)
                _restore(path, buf.getvalue())
        with pytest.raises(ArtifactError,
                           match="unreadable|integrity|decodes to"):
            load_program("m0@W2A2", store)
    finally:
        for path, payload in saved.items():
            _restore(path, payload)


def test_missing_blob_rejected(populated, tmp_path):
    root, _ = populated
    store = ArtifactStore(root)
    ref = store.resolve("m0@W2A2")
    # same manifest, separate store with no blobs at all
    empty = ArtifactStore(str(tmp_path / "empty"))
    with open(store._program_path(ref), "rb") as f:
        empty._atomic_write(empty._program_path(ref), f.read())
    with pytest.raises(ArtifactError, match="missing blob"):
        load_program(ref, empty)


def test_tampered_manifest_rejected(populated):
    root, _ = populated
    store = ArtifactStore(root)
    ref = store.resolve("m0@W2A2")
    path = store._program_path(ref)
    with open(path, "rb") as f:
        payload = f.read()
    try:
        _restore(path, payload.replace(b'"m0"', b'"mx"', 1))
        with pytest.raises(ArtifactError, match="integrity"):
            load_program(ref, store)
    finally:
        _restore(path, payload)


def test_version_bump_rejected(populated):
    root, _ = populated
    store = ArtifactStore(root)
    manifest = store.get_program(store.resolve("m0@W2A2"))
    manifest["version"] += 1
    future_ref = store.put_program(manifest)  # content-addressed: new ref
    with pytest.raises(ArtifactError, match="format version"):
        load_program(future_ref, store)


def test_wrong_format_rejected(populated):
    root, _ = populated
    store = ArtifactStore(root)
    payload = json.dumps({"format": "other", "version": 1}).encode()
    import hashlib
    ref = hashlib.sha256(payload).hexdigest()
    store._atomic_write(store._program_path(ref), payload)
    with pytest.raises(ArtifactError, match="not a repro-program-artifact"):
        load_program(ref, store)


# -------------------------------------------------------- registry + store

def test_warm_boot_zero_compiles_zero_autotuning(populated):
    root, outs = populated
    tuning.clear_cache()           # fresh L1, as a restarted process has
    reg = ModelRegistry(store=root)
    keys = _register_all(reg)
    report = reg.warm_boot()
    assert len(report["restored"]) == 4 and not report["compiled"]
    assert reg.compiles == 0 and reg.artifact_hits == 4
    assert tuning.cache_info()["enumerations"] == 0
    x = _x()
    for k in keys:
        np.testing.assert_array_equal(np.asarray(reg.program(k)(x)),
                                      outs[str(k)])
    st = reg.stats()
    assert st["artifact_hits"] == 4
    assert st["artifact_store"]["loads"] == 4
    assert st["artifact_store"]["load_p50_ms"] > 0


def test_register_artifact_needs_no_recipe(populated):
    root, outs = populated
    reg = ModelRegistry(store=root)
    key = reg.register_artifact("m1", precision="W2A2")
    np.testing.assert_array_equal(np.asarray(reg.program(key)(_x())),
                                  outs["m1@W2A2"])
    assert reg.compiles == 0
    with pytest.raises(ArtifactError, match="no artifact tagged"):
        reg.register_artifact("ghost", precision="W2A2")
    with pytest.raises(ValueError, match="requires a registry store"):
        ModelRegistry().register_artifact("m1", precision="W2A2")


def test_eviction_readmits_via_load_not_recompile(populated):
    root, outs = populated
    reg = ModelRegistry(store=root, max_programs=1)
    k_a2, k_a8 = _register_all(reg)[:2]   # m0@W2A2, m0@W2A8
    x = _x()
    y_a2 = np.asarray(reg.program(k_a2)(x))
    reg.program(k_a8)                      # evicts m0@W2A2
    assert reg.evictions == 1 and reg.artifact_spills == 1
    loads_before = reg.store.loads
    np.testing.assert_array_equal(np.asarray(reg.program(k_a2)(x)), y_a2)
    assert reg.compiles == 0               # re-admission was a disk load
    assert reg.store.loads == loads_before + 1


def test_eviction_keeps_planes_shared_with_siblings(populated):
    """Regression (LRU x artifact interplay): evicting a Program must not
    orphan a packed plane a sibling precision variant still holds, and a
    re-admitted Program must re-share the *same* array objects instead of
    duplicating device memory."""
    root, _ = populated
    reg = ModelRegistry(store=root, max_programs=1)
    k_a2, k_a8 = _register_all(reg)[:2]
    p_a2 = reg.program(k_a2)
    p_a8 = reg.program(k_a8)               # dedups against p_a2, evicts it
    shared = [k for k, rec in p_a8.params.items() if "w_packed" in rec]
    assert shared and reg.shared_arrays >= len(shared)
    # sibling's planes survive the eviction (p_a8 holds the references)
    np.testing.assert_array_equal(np.asarray(p_a8(_x())),
                                  np.asarray(p_a8(_x())))
    p_a2_again = reg.program(k_a2)         # loads from disk, evicts p_a8
    for k in shared:
        assert p_a2_again.params[k]["w_packed"] is \
            p_a8.params[k]["w_packed"], \
            "re-admitted Program duplicated a plane its sibling holds"


# ------------------------------------------------------- tuning L2 store

def test_tuning_decisions_persist_across_restart(tmp_path):
    from repro.core.bitserial import SerialSpec
    store = ArtifactStore(str(tmp_path / "tstore"))
    spec = SerialSpec(a_bits=3, w_bits=3, radix_bits=7)
    old = tuning.set_persistent_store(store)
    try:
        tuning.clear_cache()
        cfg = tuning.choose_tile(192, 320, 192, spec)
        info = tuning.cache_info()
        assert info["enumerations"] == 1 and info["persist_hits"] == 0
        tuning.clear_cache()               # simulated restart: empty L1
        cfg2 = tuning.choose_tile(192, 320, 192, spec)
        info = tuning.cache_info()
        assert info["enumerations"] == 0 and info["persist_hits"] == 1
        assert cfg2 == cfg
        # conv path too
        kw = dict(fh=3, fw=3, stride=1, padding=1, spec=spec)
        ccfg = tuning.choose_conv_tile(2, 8, 8, 8, 16, **kw)
        tuning.clear_cache()
        assert tuning.choose_conv_tile(2, 8, 8, 8, 16, **kw) == ccfg
        assert tuning.cache_info()["enumerations"] == 0
    finally:
        tuning.set_persistent_store(old)
        tuning.clear_cache()


def test_tuning_corrupt_record_retunes(tmp_path):
    from repro.core.bitserial import SerialSpec
    store = ArtifactStore(str(tmp_path / "tstore"))
    spec = SerialSpec(a_bits=2, w_bits=2, radix_bits=7)
    old = tuning.set_persistent_store(store)
    try:
        tuning.clear_cache()
        tuning.choose_tile(128, 128, 128, spec)
        for n in os.listdir(os.path.join(store.root, "tuning")):
            _restore(os.path.join(store.root, "tuning", n), b"{broken")
        tuning.clear_cache()
        tuning.choose_tile(128, 128, 128, spec)  # just re-tunes, no raise
        assert tuning.cache_info()["enumerations"] == 1
    finally:
        tuning.set_persistent_store(old)
        tuning.clear_cache()


# ------------------------------------------------------- service surface

def test_service_metrics_expose_store(populated):
    from repro.serving import InferenceService
    root, outs = populated
    reg = ModelRegistry(store=root)
    keys = _register_all(reg)
    with InferenceService(reg, max_wait_s=0.0) as svc:
        report = svc.warm_boot()
        assert len(report["restored"]) == 4
        assert report["bucket_compiles"] >= 1
        f = svc.submit(keys[0], _x(1)[0])
        # expected row: the same restored Program at the same batch size
        # (bucket 1), not row 0 of the fixture's batch-2 run — XLA may
        # round the float epilogue differently across batch shapes
        want = np.asarray(reg.program(keys[0])(_x(1)))[0]
        np.testing.assert_array_equal(np.asarray(f.result(timeout=60)),
                                      want)
        m = svc.metrics()
    assert reg.compiles == 0
    assert m["artifact_store"]["loads"] >= 4
    assert m["registry"]["artifact_hits"] == 4


# ------------------------------------------------------------ garbage gc

@pytest.fixture()
def gc_store(tmp_path):
    """A fresh store with 2 models x 2 precisions; same-w_bits variants of
    one model share packed-weight blobs on disk."""
    root = str(tmp_path / "gcstore")
    reg = ModelRegistry(store=root)
    keys = _register_all(reg)
    outs = {str(k): np.asarray(reg.program(k)(_x())) for k in keys}
    return ArtifactStore(root), keys, outs


def test_gc_noop_when_everything_tagged(gc_store):
    store, keys, _ = gc_store
    before = store.stats()
    rep = store.gc()
    assert rep["removed_programs"] == 0 and rep["removed_blobs"] == 0
    assert rep["bytes_freed"] == 0
    assert rep["live_programs"] == len(set(store.tags().values()))
    assert store.stats() == before


def test_gc_dry_run_reports_without_deleting(gc_store):
    store, keys, _ = gc_store
    assert store.untag(str(keys[0]))
    assert not store.untag(str(keys[0]))        # idempotent: already gone
    before = store.stats()
    rep = store.gc(dry_run=True)
    assert rep["dry_run"] is True
    assert rep["removed_programs"] == 1
    assert rep["bytes_freed"] > 0
    # nothing touched: the dead manifest and its blobs are all still there
    assert store.stats() == before
    live = store.gc()                            # now collect for real
    assert live["removed_programs"] == 1
    assert live["bytes_freed"] >= rep["bytes_freed"]


def test_gc_keeps_blobs_shared_with_surviving_tags(gc_store):
    """m0@W2A2 and m0@W2A8 share packed planes (same w_bits). Untagging
    one precision must only reclaim its unique blobs — the survivor still
    loads bit-exact afterwards."""
    store, keys, outs = gc_store
    k_dead, k_live = keys[0], keys[1]            # m0 at W2A2 / W2A8
    blobs_before = store.stats()["blobs"]
    store.untag(str(k_dead))
    rep = store.gc()
    assert rep["removed_programs"] == 1
    # shared planes survive; only variant-unique blobs (if any) go
    assert store.stats()["blobs"] == blobs_before - rep["removed_blobs"]
    prog = load_program(str(k_live), store)
    np.testing.assert_array_equal(np.asarray(prog(_x())),
                                  outs[str(k_live)])


def test_gc_collects_fully_untagged_model(gc_store):
    store, keys, outs = gc_store
    st0 = store.stats()
    for k in keys[2:]:                           # drop m1 entirely
        assert store.untag(str(k))
    rep = store.gc()
    assert rep["removed_programs"] == 2
    assert rep["removed_blobs"] > 0              # m1's planes orphaned
    assert rep["bytes_freed"] > 0
    st = store.stats()
    assert st["programs"] == st0["programs"] - 2
    assert st["blobs"] == st0["blobs"] - rep["removed_blobs"]
    # the untouched model still round-trips
    for k in keys[:2]:
        prog = load_program(str(k), store)
        np.testing.assert_array_equal(np.asarray(prog(_x())),
                                      outs[str(k)])
    # second pass finds nothing left to reclaim
    assert store.gc()["removed_programs"] == 0
    assert store.gc()["removed_blobs"] == 0


def test_gc_keeps_unreadable_but_tagged_manifest(gc_store):
    store, keys, _ = gc_store
    ref = store.resolve(str(keys[0]))
    path = os.path.join(store.root, "programs", f"{ref}.json")
    _restore(path, b"{not json")
    rep = store.gc()                             # conservatively kept
    assert rep["removed_programs"] == 0
    assert os.path.exists(path)


@pytest.mark.slow
def test_compile_cli_gc_flags(tmp_path, capsys):
    """`launch.serve compile --gc[-dry-run]` end to end: compile one
    variant, orphan it by tagging churn, and let the CLI reclaim it."""
    from repro.launch.serve import _main_compile
    root = str(tmp_path / "clistore")
    base = ["--arch", "resnet9-cifar10", "--store", root,
            "--precisions", "W2A2", "--calib-batch", "2"]
    _main_compile(base + ["--gc-dry-run"])
    out = capsys.readouterr().out
    assert "gc dry-run: removed_programs=0" in out
    store = ArtifactStore(root)
    # orphan the artifact, then re-run with --gc: the fresh compile's save
    # re-tags the same content, so gc only sweeps true garbage
    name = next(iter(store.tags()))
    store.untag(name)
    _main_compile(base + ["--gc"])
    out = capsys.readouterr().out
    assert "(store hit)" in out or "(compiled)" in out
    assert "gc: removed_programs=0" in out       # re-tagged == reachable
    assert store.stats()["programs"] == 1
