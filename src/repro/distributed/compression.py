"""Gradient compression for the data-parallel axes: int8 quantized
reduce-scatter/all-gather with error feedback — the paper's quantizer/
serializer applied to the *gradient* channel.

Wire format: a ring all-reduce of fp32 moves ``2·N·4`` bytes per device;
the compressed exchange moves ``2·N·1`` bytes (int8 codes; per-chunk fp32
scales are negligible) — a 4x collective-bytes reduction, visible in the
dry-run HLO. Error feedback (Karimireddy et al. 2019) keeps SGD unbiased in
the long run: the quantization residual is added back before the next
step's compression.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["compressed_allreduce_mean", "compress_tree", "init_error_state"]


def _quant(x) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_allreduce_mean(g: jax.Array, axis_name: str) -> jax.Array:
    """Mean over ``axis_name`` exchanging int8 codes on the wire.

    reduce-scatter phase: each device quantizes its shard-chunk to int8 and
    all-to-alls the codes; local sum in int32. all-gather phase: the reduced
    chunk is requantized to int8 and all-gathered. Must run inside
    ``shard_map`` (manual axes).
    """
    n = jax.lax.axis_size(axis_name)
    flat = g.reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    q, scale = _quant(chunks)
    # all_to_all: device d receives chunk d from every peer (int8 on wire)
    recv = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                              tiled=True).reshape(n, -1)
    scales = jax.lax.all_gather(scale, axis_name)          # (n,) fp32 scalars
    local_sum = jnp.sum(recv.astype(jnp.float32)
                        * scales[:, None], axis=0) / n
    # second phase: requantize the reduced chunk, all-gather codes
    q2, s2 = _quant(local_sum)
    gathered = jax.lax.all_gather(q2, axis_name)           # (n, chunk) int8
    s2g = jax.lax.all_gather(s2, axis_name)
    out = (gathered.astype(jnp.float32) * s2g[:, None]).reshape(-1)
    out = out[:g.size].reshape(g.shape)
    return out.astype(g.dtype)


def init_error_state(grads):
    return jax.tree.map(jnp.zeros_like, grads)


def compress_tree(grads, err, axis_name: str):
    """Error-feedback compressed mean-reduce of a gradient pytree (inside
    shard_map over the DP axis). Returns (reduced_grads, new_err)."""
    def one(g, e):
        corrected = g.astype(jnp.float32) + e
        reduced = compressed_allreduce_mean(corrected, axis_name)
        # residual of OUR contribution (local quantization error)
        q, s = _quant(corrected.reshape(-1))
        recon = (q.astype(jnp.float32) * s).reshape(g.shape)
        new_e = corrected - recon
        return reduced.astype(g.dtype), new_e

    pairs = jax.tree.map(one, grads, err,
                         is_leaf=lambda x: isinstance(x, jax.Array))
    reduced = jax.tree.map(lambda p: p[0], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda p: p[1], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
    return reduced, new_err
