"""Pipeline parallelism — BARVINN's Pipelined mode on the pod axis.

The FPGA streams layer outputs MVU→MVU over an 8-way crossbar so downstream
layers start before upstream ones finish the whole tensor (§3.1.6). The ICI
analogue is GPipe microbatching: consecutive layer groups live on
consecutive ``pp``-axis shards, activations move with
``lax.ppermute`` (the crossbar), and microbatch ``m`` occupies stage ``s``
at step ``m+s`` — the same wavefront the paper draws in Figure 5(a).

Implemented with ``shard_map`` over the stage axis; other mesh axes stay
automatic so TP/DP compose inside each stage.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe", "stage_stack"]


def stage_stack(tree, n_stages: int):
    """Re-stack per-layer params (L, ...) into (n_stages, L/S, ...)."""
    def f(x):
        l = x.shape[0]
        if n_stages < 1 or l % n_stages != 0:
            # a bare assert here vanishes under `python -O` and the reshape
            # below then silently folds layers across stage boundaries
            raise ValueError(
                f"stage_stack: leading (layer) dim {l} is not divisible "
                f"by n_stages={n_stages} (leaf shape {x.shape})")
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])
    return jax.tree.map(f, tree)


def gpipe(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
          stage_axis: str = "pod", n_microbatches: int = None):
    """Run ``y = stages(x)`` through a GPipe wavefront.

    ``stage_fn(params_for_stage, microbatch) -> microbatch`` applies one
    stage's layers. ``stage_params``: leaves with leading dim = n_stages.
    ``x``: (batch, ...) activations; split into ``n_microbatches`` along
    batch. Returns (batch, ...) outputs from the last stage.
    """
    n_stages = mesh.shape[stage_axis]
    nm = n_microbatches or n_stages
    b = x.shape[0]
    if nm < 1 or b % nm != 0:
        raise ValueError(
            f"gpipe: batch {b} is not divisible into n_microbatches={nm} "
            f"(stage_axis={stage_axis!r} has {n_stages} stages); pad the "
            f"batch or pick n_microbatches dividing it")
    mb = b // nm
    xm = x.reshape((nm, mb) + x.shape[1:])

    in_specs = (jax.tree.map(lambda _: P(stage_axis), stage_params),
                P(None))
    out_specs = P(None)
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def run(params, xms):
        # params leaves: (1, L/S, ...) — this stage's slice
        params = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(stage_axis)
        steps = nm + n_stages - 1
        carry = jnp.zeros((mb,) + xms.shape[2:], xms.dtype)
        outs = jnp.zeros_like(xms)
        for t in range(steps):
            # stage 0 ingests microbatch t; other stages use the permuted
            # carry arriving from the previous stage (the crossbar write)
            feed = jnp.where(idx == 0,
                             xms[min(t, nm - 1)] if t < nm else carry,
                             carry)
            out = stage_fn(params, feed)
            m_idx = t - idx  # which microbatch this stage just produced
            is_last = idx == n_stages - 1
            valid = jnp.logical_and(is_last,
                                    jnp.logical_and(m_idx >= 0, m_idx < nm))
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, out[None], jnp.maximum(m_idx, 0), 0),
                lambda o: o, outs)
            carry = jax.lax.ppermute(out, stage_axis, fwd)
        # last stage holds the real outputs; broadcast to all stages
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    ym = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs,
                       axis_names=frozenset({stage_axis}),
                       check_vma=False)(stage_params, xm)
    return ym.reshape((b,) + x.shape[1:])
