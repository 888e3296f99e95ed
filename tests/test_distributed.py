"""Distribution tests: run in subprocesses with 8 fake CPU devices so the
main test process keeps its single-device view (per the dry-run contract)."""

import pytest

from _subproc import run_with_devices

# every test here spawns a fresh interpreter + 8 fake devices and compiles
# a model from scratch: the subprocess-mesh tier (CI runs it in the
# dedicated distributed step and the nightly slow job)
pytestmark = pytest.mark.slow


def test_param_shardings_resolve():
    run_with_devices("""
        from repro.configs import get_arch
        from repro.models.transformer import init_params
        from repro.distributed.sharding import tree_shardings, tree_pspecs
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = get_arch("qwen3-moe-235b-a22b").smoke
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        specs = tree_pspecs(shapes, mesh)
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        # experts must be sharded over model (EP)
        found_ep = any("moe" in "/".join(str(p) for p in kp) and
                       "model" in str(s) for kp, s in flat)
        assert found_ep, "no EP sharding found"
        sh = tree_shardings(shapes, mesh)
        assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(shapes))
    """)


def test_sharded_train_step_runs():
    """Real sharded train step on an 8-device mesh (2 data x 4 model)."""
    run_with_devices("""
        from repro.configs import get_arch
        from repro.models.transformer import init_params, loss_fn
        from repro.distributed.sharding import tree_shardings, batch_pspec
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = get_arch("stablelm-1.6b").smoke
        params = init_params(jax.random.PRNGKey(0), cfg)
        shardings = tree_shardings(params, mesh)
        params = jax.device_put(params, shardings)
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 16))),
                 "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 16)))}
        bspec = {k: NamedSharding(mesh, batch_pspec(v.shape, mesh))
                 for k, v in batch.items()}
        batch = jax.device_put(batch, bspec)
        with mesh:
            loss, grads = jax.jit(jax.value_and_grad(
                lambda p, b: loss_fn(p, b, cfg)[0]))(params, batch)
        assert np.isfinite(float(loss))
        gn = sum(float(jnp.sum(g.astype(jnp.float32)**2))
                 for g in jax.tree.leaves(grads))
        assert np.isfinite(gn) and gn > 0
    """)


def test_gpipe_matches_sequential():
    run_with_devices("""
        from repro.distributed.pipeline_parallel import gpipe, stage_stack
        mesh = jax.make_mesh((4, 2), ("pod", "model"))
        L, D = 8, 16
        rng = np.random.RandomState(0)
        ws = jnp.asarray(rng.randn(L, D, D) / np.sqrt(D), jnp.float32)
        x = jnp.asarray(rng.randn(16, D), jnp.float32)

        def layer(w, h):
            return jnp.tanh(h @ w)

        def seq(ws, x):
            for i in range(L):
                x = layer(ws[i], x)
            return x

        def stage_fn(wstage, h):  # wstage: (L/4, D, D)
            def body(hh, w):
                return layer(w, hh), None
            out, _ = jax.lax.scan(body, h, wstage)
            return out

        ref = seq(ws, x)
        y = jax.jit(lambda w, x: gpipe(stage_fn, stage_stack(w, 4), x,
                                       mesh=mesh, stage_axis="pod",
                                       n_microbatches=4))(ws, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
    """)


def test_compressed_allreduce():
    run_with_devices("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import (compressed_allreduce_mean,
                                                   compress_tree,
                                                   init_error_state)
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(8, 64), jnp.float32)

        f = jax.shard_map(
            partial(compressed_allreduce_mean, axis_name="data"),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False)
        out = jax.jit(f)(g)
        ref = jnp.broadcast_to(jnp.mean(g, 0, keepdims=True), g.shape)
        rel = float(jnp.abs(out - ref).max() / (jnp.abs(ref).max() + 1e-9))
        assert rel < 0.05, rel  # int8 wire precision

        # error feedback path runs and stays finite
        def step(err, g):
            red, err = compress_tree({"g": g}, err, "data")
            return err, red["g"]
        f2 = jax.shard_map(
            lambda g: step(init_error_state({"g": g}), g)[1],
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False)
        out2 = jax.jit(f2)(g)
        assert np.isfinite(np.asarray(out2)).all()
    """)


def test_moe_ep_sharded_forward():
    run_with_devices("""
        from repro.configs import get_arch
        from repro.models.transformer import init_params, forward
        from repro.distributed.sharding import tree_shardings
        from repro.distributed import context
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = get_arch("deepseek-v2-lite-16b").smoke
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, tree_shardings(params, mesh))
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 16))),
                 "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 16)))}
        with mesh:
            with context.bind_axes(dp=("data",), tp="model"):
                logits, _ = jax.jit(lambda p, b: forward(p, b, cfg))(params, batch)
        assert np.isfinite(np.asarray(logits)).all()
    """)
