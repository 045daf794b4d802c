"""Lane primitives shared by the token kernels and their jnp oracles.

Every op here lowers both through XLA and through Mosaic (the Pallas TPU
compiler), so a kernel and its oracle call the *same* function and run one
op sequence on every backend.  Only the lane rotation differs by caller:
``jnp.roll`` in XLA, ``pltpu.roll`` inside a kernel — pure data movement,
so the float results cannot differ.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def prefix_sum(x: jnp.ndarray, roll=jnp.roll) -> jnp.ndarray:
    """Inclusive prefix sum along the last axis, log-step (Hillis–Steele).

    Step ``k`` adds the value ``k`` lanes to the left, for ``k = 1, 2, 4,
    ...``: ``ceil(log2 n)`` rotate-and-add passes, no ``[n, n]`` matrix.
    Lane ``i`` only ever reads lanes ``<= i``, so zero lanes padded on the
    right (and the extra passes they bring) leave lanes ``< n`` bit-equal —
    which is what lets a lane-padded kernel match the unpadded oracle.
    ``roll(x, shift, axis)`` must rotate like ``jnp.roll``.
    """
    axis = x.ndim - 1
    n = x.shape[axis]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    k = 1
    while k < n:
        x = jnp.where(lane >= k, x + roll(x, k, axis), x)
        k *= 2
    return x


def first_index(mask: jnp.ndarray) -> jnp.ndarray:
    """i32[..., 1]: the first lane where ``mask`` holds (``n`` if none).

    A float min over lane indices: Mosaic reduces only f32 to an index, and
    this form ties to the lowest lane by construction, like ``jnp.argmax``
    on a bool mask."""
    n = mask.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)
    pos = jnp.where(mask, lane.astype(jnp.float32), jnp.float32(n))
    return jnp.min(pos, axis=-1, keepdims=True).astype(jnp.int32)
