"""Validation of the HLO call-graph cost analyzer against closed-form
examples (the §Roofline numbers depend on it)."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, n: int = 8):
    src = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.hlo_analysis import analyze_hlo
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROC_OK" in out.stdout


def test_scan_flops_exact():
    _run("""
        def body(c, w):
            return jnp.tanh(c @ w), None
        def f(x, ws):
            return jax.lax.scan(body, x, ws)[0]
        x = jax.ShapeDtypeStruct((128, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((24, 64, 64), jnp.float32)
        comp = jax.jit(f).lower(x, ws).compile()
        cost = analyze_hlo(comp.as_text())
        expect = 2 * 128 * 64 * 64 * 24
        assert abs(cost.flops - expect) / expect < 1e-6, cost.flops
        assert 24 in cost.while_trips
    """)


def test_collectives_counted_per_iteration():
    _run("""
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((8,), ("data",))
        def body(c, w):
            return jax.lax.psum(jnp.tanh(c @ w), "data"), None
        def g(x, ws):
            return jax.lax.scan(body, x, ws)[0]
        gm = jax.shard_map(g, mesh=mesh,
                           in_specs=(P(None, None), P(None, None, None)),
                           out_specs=P(None, None), check_vma=False)
        x = jax.ShapeDtypeStruct((128, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((24, 64, 64), jnp.float32)
        comp = jax.jit(gm).lower(x, ws).compile()
        cost = analyze_hlo(comp.as_text())
        assert cost.collective_counts["all-reduce"] == 24, cost.collective_counts
        assert abs(cost.collective_bytes["all-reduce"]
                   - 24 * 128 * 64 * 4) / (24 * 128 * 64 * 4) < 0.01
    """)


def test_nested_while_multiplies():
    _run("""
        def inner(c, w):
            return c @ w, None
        def outer(c, ws):
            c2, _ = jax.lax.scan(inner, c, ws)
            return c2, None
        def f(x, ws):  # 4 outer x 6 inner = 24 dots
            return jax.lax.scan(lambda c, _: outer(c, ws), x,
                                jnp.arange(4))[0]
        x = jax.ShapeDtypeStruct((32, 16), jnp.float32)
        ws = jax.ShapeDtypeStruct((6, 16, 16), jnp.float32)
        comp = jax.jit(f).lower(x, ws).compile()
        cost = analyze_hlo(comp.as_text())
        expect = 2 * 32 * 16 * 16 * 24
        assert abs(cost.flops - expect) / expect < 1e-6, cost.flops
    """)


def test_dus_counts_slice_not_buffer():
    _run("""
        def f(buf, upd):
            return jax.lax.dynamic_update_slice(buf, upd, (0, 0))
        buf = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)
        upd = jax.ShapeDtypeStruct((4, 4096), jnp.float32)
        comp = jax.jit(f, donate_argnums=(0,)).lower(buf, upd).compile()
        cost = analyze_hlo(comp.as_text())
        # must charge ~the update slice, not 2x the 64MB buffer
        assert cost.bytes_hbm < 4096 * 4096 * 4, cost.bytes_hbm
    """)


def test_int_dot_classified():
    _run("""
        def f(a, b):
            return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.int32)
        a = jax.ShapeDtypeStruct((64, 128), jnp.int8)
        b = jax.ShapeDtypeStruct((128, 32), jnp.int8)
        comp = jax.jit(f).lower(a, b).compile()
        cost = analyze_hlo(comp.as_text())
        assert cost.flops_int == cost.flops > 0
    """)
