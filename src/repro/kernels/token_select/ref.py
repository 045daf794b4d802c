"""Pure-jnp oracle for the token_select kernel.

:func:`weighted_draw` is the statistical-token draw itself (opportunity
renormalization -> uniform fallback -> segment search -> demand guard).  The
oracle, the token_select kernel and the fused tick-step kernel all call it,
so every draw path runs one op sequence; ``select_job`` delegates here
through the :mod:`.ops` dispatcher, so the oracle IS the production draw
path off TPU and the bit-identity bar for the Pallas kernels on it.

The draw uses only rotations, adds, multiplies, compares and selects — no
division and no order-dependent float reduction — so XLA and Mosaic agree
bit for bit: the segment table and its total come from one shared prefix
sum (:func:`repro.kernels.prefix.prefix_sum`), and ``u`` is scaled by that
total instead of dividing the table by it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..prefix import first_index, prefix_sum


def weighted_draw(shares: jnp.ndarray, demand: jnp.ndarray, u: jnp.ndarray,
                  real_j: int | None = None, roll=jnp.roll) -> jnp.ndarray:
    """shares: [S, Jp]; demand: bool[S, Jp]; u: f32[S, W] -> i32[S, W].

    Each of the W draws picks the job whose segment of the demand-masked
    share table contains ``u`` (scaled to the table's total), falling back
    to uniform-over-demanded when no demanded job has share mass; -1 when
    nothing is demanded.  ``real_j`` is the unpadded job count: lanes at or
    past it never count in the segment search, so a lane-padded kernel
    resolves every draw as the unpadded oracle does.  ``roll`` is the lane
    rotation (``pltpu.roll`` inside a kernel).
    """
    real_j = shares.shape[-1] if real_j is None else real_j
    lane = jax.lax.broadcasted_iota(jnp.int32, shares.shape, 1)
    dm = demand.astype(jnp.float32)
    masked = shares.astype(jnp.float32) * dm
    # Work conservation: demand with no policy mass draws uniformly.
    has_mass = jnp.any(masked > 0, axis=-1, keepdims=True)
    seg = prefix_sum(jnp.where(has_mass, masked, dm), roll)      # [S, Jp]
    total = jax.lax.slice_in_dim(seg, real_j - 1, real_j, axis=1)  # [S, 1]
    # Branchless segment search per draw: count real boundaries <= u·total.
    below = ((seg[:, None, :] <= (u * total)[:, :, None])
             & (lane[:, None, :] < real_j))
    idx = jnp.sum(below.astype(jnp.int32), axis=-1)               # [S, W]
    idx = jnp.minimum(idx, real_j - 1)
    idx = jnp.where(total > 0, idx, -1)
    # Roundoff guard: the picked slot must have demand (u·total can round up
    # onto the last boundary); else take the first demanded slot.
    has = jnp.sum(jnp.where(lane[:, None, :] == idx[:, :, None],
                            demand[:, None, :].astype(jnp.int32), 0), axis=-1)
    return jnp.where((idx >= 0) & (has == 0), first_index(demand), idx)


def token_select_ref(shares: jnp.ndarray, qcount: jnp.ndarray,
                     u: jnp.ndarray) -> jnp.ndarray:
    """shares, qcount: [S, J]; u: [S, W] -> int32 [S, W] (-1 = idle)."""
    return weighted_draw(shares, qcount > 0, u)
