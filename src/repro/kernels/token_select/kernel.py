"""Pallas TPU kernel: fused statistical-token worker draw (paper §3 hot path).

The paper's I/O worker pops one token at a time: draw u ~ U[0,1), walk the
job segment table, pop that job's queue.  The lock-free-queue formulation
does not transfer to TPU (no mutexes, no dynamic queues in VMEM); the
TPU-native equivalent of the same statistics is a *fused masked weighted
choice* over a fixed job-slot table:

    mask   = qcount > 0                       (opportunity fairness)
    weight = shares * mask                    (uniform over demanded jobs
    seg    = inclusive prefix-sum(weight)      when massless)
    pick   = count(seg <= u * seg[J-1])

One grid step processes a block of servers; the segment table lives in VMEM
(jobs padded to the 128-lane width), and all W worker draws for the block are
answered branchlessly in one pass.  The body is
:func:`~.ref.weighted_draw` — the oracle's own function, with the lane
rotation bound to ``pltpu.roll`` and the segment search clipped to the real
J — so kernel and oracle are one op sequence and agree bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import weighted_draw


def _token_select_kernel(shares_ref, qcount_ref, u_ref, out_ref, *,
                         real_j: int):
    out_ref[...] = weighted_draw(shares_ref[...], qcount_ref[...] > 0,
                                 u_ref[...], real_j, roll=pltpu.roll)


def token_select_pallas(shares: jnp.ndarray, qcount: jnp.ndarray,
                        u: jnp.ndarray, *, block_servers: int = 8,
                        interpret: bool = True) -> jnp.ndarray:
    """shares, qcount: [S, J]; u: [S, W] -> int32 [S, W] (-1 = idle).

    J is padded to the 128-lane width inside; S is blocked over the grid.
    ``interpret=True`` runs the kernel body on CPU (validation mode); on a
    real TPU pass interpret=False.
    """
    s, j = shares.shape
    w = u.shape[1]
    jp = -(-j // 128) * 128
    sp = -(-s // block_servers) * block_servers
    shares_p = jnp.zeros((sp, jp), jnp.float32).at[:s, :j].set(shares)
    qcount_p = jnp.zeros((sp, jp), jnp.int32).at[:s, :j].set(qcount)
    u_p = jnp.zeros((sp, w), jnp.float32).at[:s].set(u)
    grid = (sp // block_servers,)
    out = pl.pallas_call(
        functools.partial(_token_select_kernel, real_j=j),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_servers, jp), lambda i: (i, 0)),
            pl.BlockSpec((block_servers, jp), lambda i: (i, 0)),
            pl.BlockSpec((block_servers, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_servers, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, w), jnp.int32),
        interpret=interpret,
    )(shares_p, qcount_p, u_p)
    return out[:s]
