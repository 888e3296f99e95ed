"""What every model's plain reference shares: seeded keys and images, the
LSQ step and codes, and a forward run block by block.

Nothing here imports the system under test. A model module
(``bench/models/<model>.py``) builds its reference from these pieces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int, stream: int):
    """A JAX key from a seed of any size, 32 bits or more."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def make_images(seed: int, n: int, hw: int, c: int, stream: int) -> np.ndarray:
    """``n`` images of ``hw`` x ``hw`` x ``c`` in [0, 1), float32, on the
    host."""
    rng = np.random.default_rng([int(seed), stream])
    return rng.random((n, hw, hw, c), dtype=np.float32)


def qrange(bits: int):
    return -(1 << (bits - 1)), (1 << (bits - 1)) - 1


def lsq_step(x, bits: int, axis=None):
    """The LSQ initial step ``2 * mean|x| / sqrt(Qp) + 1e-8``, per tensor
    or over ``axis``."""
    _, qp = qrange(bits)
    m = jnp.mean(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return 2.0 * m / np.sqrt(max(qp, 1)) + 1e-8


def lsq_codes(x, step, bits: int):
    """Signed codes ``clip(round(x / step), Qn, Qp)``, half to even."""
    qn, qp = qrange(bits)
    return jnp.clip(jnp.round(x / step), qn, qp)


def blocked(forward, params, cal, images, cfg, *, dtype,
            block: int) -> np.ndarray:
    """``forward(params, cal, images, cfg, dtype)`` over ``images`` (N, H,
    W, C), ``block`` images at a time so that the whole set never sits on
    the device; one compiled shape for every block."""
    fwd = jax.jit(forward, static_argnums=(3, 4))
    out = []
    for i in range(0, len(images), block):
        chunk = images[i:i + block]
        n = len(chunk)
        if n < block:
            chunk = np.concatenate(
                [chunk, np.zeros((block - n,) + chunk.shape[1:],
                                 chunk.dtype)])
        out.append(np.asarray(fwd(params, cal, jnp.asarray(chunk),
                                  Frozen(cfg), dtype))[:n])
    return np.concatenate(out) if out else np.zeros((0, cfg["num_classes"]))


class Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(repr(sorted((k, repr(v)) for k, v in self.items())))
