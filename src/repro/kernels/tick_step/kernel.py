"""Pallas TPU kernel: the engine tick's whole worker phase, fused.

The engine's hot path is a W-step ``lax.scan`` of O(S·J) gathers/scatters —
per worker: mask demand, renormalize the share table, prefix-sum, segment
search, pop, advance the ring head.  This kernel answers all W draws in ONE
invocation: the ``[S, J]`` queue state stays in VMEM across the (statically
unrolled) worker loop, so the share table is loaded once per server block
instead of W times, and nothing round-trips to HBM between workers.

Two select modes are lowered (the capability the scheduler registry flags
with ``Scheduler.kernel_tick``):

  * ``themis`` — the statistical-token weighted draw: the body calls
    :func:`repro.kernels.token_select.ref.weighted_draw`, the function
    ``token_select`` / ``core.tokens.select_job`` draw through;
  * ``fifo``   — earliest queued arrival, over a precomputed ``[S, J, W]``
    window of the next W ring stamps (the at-most-W pops a tick can take).

ref.py is the pure-jnp oracle; the engine equivalence tests hold this
kernel bit-identical to the legacy scan for every lowered scheduler.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..prefix import first_index
from ..token_select.ref import weighted_draw
from .ref import MODES

#: The kernel's name in lowered programs and in profiler traces.
KERNEL_NAME = "tick_step_pallas"


def _tick_step_kernel(shares_ref, qcount_ref, window_ref, free_ref, u_ref,
                      sel_ref, valid_ref, dany_ref, qout_ref, pops_ref, *,
                      mode: str, real_j: int, n_workers: int):
    shares = shares_ref[...]                         # [BS, Jp]
    qcount = qcount_ref[...]                         # [BS, Jp] live counts
    free = free_ref[...] > 0                         # [BS, W]
    u = u_ref[...]                                   # [BS, W]
    pops = jnp.zeros_like(qcount)                    # ring advance so far
    jidx = jax.lax.broadcasted_iota(jnp.int32, qcount.shape, 1)
    widx = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    sel = jnp.zeros(u.shape, jnp.int32)
    valid_all = jnp.zeros(u.shape, jnp.int32)
    dany_all = jnp.zeros(u.shape, jnp.int32)
    if mode == "fifo":
        window = window_ref[...]                     # [BS, Jp, W]
        kidx = jax.lax.broadcasted_iota(jnp.int32, window.shape, 2)
    for w in range(n_workers):                       # static unroll
        demand = qcount > 0
        dany = jnp.any(demand, axis=-1, keepdims=True)               # [BS, 1]
        if mode == "themis":
            j_sel = weighted_draw(
                shares, demand, jax.lax.slice_in_dim(u, w, w + 1, axis=1),
                real_j, roll=pltpu.roll)                             # [BS, 1]
        else:
            # branchless window gather at k = pops (a one-hot min; exactly
            # window[s, j, pops] — each k matches at most once)
            ht = jnp.min(jnp.where(kidx == pops[:, :, None], window, jnp.inf),
                         axis=-1)
            ht = jnp.where(demand, ht, jnp.inf)
            # earliest stamp, ties to the lowest job like jnp.argmin
            j_sel = first_index(ht == jnp.min(ht, axis=-1, keepdims=True))
            j_sel = jnp.where(dany, j_sel, -1)
        valid = jax.lax.slice_in_dim(free, w, w + 1, axis=1) & (j_sel >= 0)
        onehot = ((jidx == j_sel) & valid).astype(jnp.int32)
        qcount = qcount - onehot
        pops = pops + onehot
        here = widx == w
        sel = jnp.where(here, j_sel, sel)
        valid_all = jnp.where(here, valid.astype(jnp.int32), valid_all)
        dany_all = jnp.where(here, dany.astype(jnp.int32), dany_all)
    sel_ref[...] = sel
    valid_ref[...] = valid_all
    dany_ref[...] = dany_all
    qout_ref[...] = qcount
    pops_ref[...] = pops


def tick_step_pallas(shares: jnp.ndarray, qcount: jnp.ndarray,
                     window: jnp.ndarray, free: jnp.ndarray, u: jnp.ndarray,
                     *, mode: str = "themis", block_servers: int = 8,
                     interpret: bool = True):
    """shares, qcount: [S, J]; window: [S, J, W]; free, u: [S, W].

    Returns ``(sel i32[S,W], valid bool[S,W], demand_any bool[S,W],
    qcount_out i32[S,J], pops i32[S,J])`` — see ref.py for semantics.
    J is padded to the 128-lane width, S is blocked over the grid;
    ``interpret=True`` runs the body on CPU (validation mode).
    """
    if mode not in MODES:
        raise ValueError(f"unknown tick-step mode {mode!r}; one of {MODES}")
    s, j = qcount.shape
    w = u.shape[1]
    jp = -(-j // 128) * 128
    sp = -(-s // block_servers) * block_servers
    shares_p = jnp.zeros((sp, jp), jnp.float32).at[:s, :j].set(shares)
    qcount_p = jnp.zeros((sp, jp), jnp.int32).at[:s, :j].set(qcount)
    window_p = jnp.zeros((sp, jp, w), jnp.float32).at[:s, :j].set(window)
    free_p = jnp.zeros((sp, w), jnp.int32).at[:s].set(free.astype(jnp.int32))
    u_p = jnp.zeros((sp, w), jnp.float32).at[:s].set(u)
    grid = (sp // block_servers,)
    bs = block_servers
    sel, valid, dany, qout, pops = pl.pallas_call(
        functools.partial(_tick_step_kernel, mode=mode, real_j=j,
                          n_workers=w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, jp), lambda i: (i, 0)),
            pl.BlockSpec((bs, jp), lambda i: (i, 0)),
            pl.BlockSpec((bs, jp, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((bs, w), lambda i: (i, 0)),
            pl.BlockSpec((bs, w), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bs, w), lambda i: (i, 0)),
            pl.BlockSpec((bs, w), lambda i: (i, 0)),
            pl.BlockSpec((bs, w), lambda i: (i, 0)),
            pl.BlockSpec((bs, jp), lambda i: (i, 0)),
            pl.BlockSpec((bs, jp), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sp, w), jnp.int32),
            jax.ShapeDtypeStruct((sp, w), jnp.int32),
            jax.ShapeDtypeStruct((sp, w), jnp.int32),
            jax.ShapeDtypeStruct((sp, jp), jnp.int32),
            jax.ShapeDtypeStruct((sp, jp), jnp.int32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(shares_p, qcount_p, window_p, free_p, u_p)
    return (sel[:s], valid[:s] > 0, dany[:s] > 0, qout[:s, :j],
            pops[:s, :j])
