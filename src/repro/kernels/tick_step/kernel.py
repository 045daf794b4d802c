"""Pallas TPU kernel: the engine tick's whole worker phase, fused.

The engine's hot path is a W-step ``lax.scan`` of O(S·J) gathers/scatters —
per worker: mask demand, renormalize the share table, prefix-sum, segment
search, pop, advance the ring head.  This kernel answers all W draws in ONE
invocation: the ``[S, J]`` queue state stays in VMEM across the (statically
unrolled) worker loop, so the share table is loaded once per row block
instead of W times, and nothing round-trips to HBM between workers.

Two select modes are lowered (the capability the scheduler registry flags
with ``Scheduler.kernel_tick``):

  * ``themis`` — the statistical-token weighted draw: the body calls
    :func:`repro.kernels.token_select.ref.weighted_draw`, the function
    ``token_select`` / ``core.tokens.select_job`` draw through.  It reads no
    ring window, and takes none;
  * ``fifo``   — earliest queued arrival, over a precomputed ``[S, J, W]``
    window of the next W ring stamps (the at-most-W pops a tick can take).

Launch geometry.  Every op of the body is row-wise (reductions run along
the job axis; ``u`` and ``free`` are per row), so independent rows can
share a block bit-for-bit.  :func:`tick_step_pallas` has a ``custom_vmap``
rule that folds vmap lanes into rows — ``[B, S, ...]`` operands become
``[B·S, ...]``, nested vmaps fold recursively — so one invocation serves
every lane of ``run_batch``.  :func:`tick_step_grid` then puts all rows in
one grid step while the blocks fit :data:`VMEM_BLOCK_BUDGET` and
:data:`MAX_BLOCK_ROWS`, and splits into the fewest steps otherwise.

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
from .ref import check_mode

#: The kernel's name in lowered programs and in profiler traces.
KERNEL_NAME = "tick_step_pallas"

#: VMEM that one grid step's blocks may take: three quarters of a v5e
#: core's 16 MiB default scoped VMEM, the rest left to the body's spilled
#: temporaries.  (Mosaic's scoped allocation measured one buffer per block
#: plus 1.3 MiB: fifo at 256 rows, J=8, W=8 asks 18.5 MiB for 17.1 of
#: blocks, compiled for a described v5e.)
VMEM_BLOCK_BUDGET = 12 << 20
#: Rows per step at most.  On a v5e the kernel's time grows far slower
#: than its block (J=8, W=8: 128 rows in one step take 19.6 us, 16 steps of
#: 8 take 152.6), but past 128 rows the gain shrinks (512 rows: 61.3 us in
#: one step, 77.3 in four) while the spilled temporaries and the compile
#: time grow with the block: 1024 rows ask 23.3 MiB of scoped VMEM.
MAX_BLOCK_ROWS = 128

_SUBLANES, _LANES = 8, 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tick_step_grid(rows: int, j: int, w: int, mode: str) -> tuple[int, int]:
    """``(block_rows, steps)`` of one invocation over ``rows`` independent
    ``(lane, server)`` rows of ``J`` jobs and ``W`` workers.

    A row's blocks take ``4·Jp`` words for the four ``[rows, Jp]`` arrays
    (shares, qcount in; counts, pops out), ``5·128`` for the five
    ``[rows, W]`` ones (W pads to a full lane row in VMEM), and in fifo mode
    ``Jp·128`` for the ``[rows, Jp, W]`` window.  All rows (rounded up to a
    sublane tile) go in one step while that fits :data:`VMEM_BLOCK_BUDGET`
    and :data:`MAX_BLOCK_ROWS`, else the fewest steps of at least one tile.
    """
    jp = _round_up(j, _LANES)
    wp = _round_up(w, _LANES)
    words = 4 * jp + 5 * wp + (jp * wp if mode == "fifo" else 0)
    fit = min(MAX_BLOCK_ROWS, VMEM_BLOCK_BUDGET // (4 * words))
    fit = max(_SUBLANES, fit // _SUBLANES * _SUBLANES)
    rows8 = _round_up(max(rows, 1), _SUBLANES)
    steps = -(-rows8 // fit)
    return _round_up(-(-rows8 // steps), _SUBLANES), steps


def _tick_step_kernel(*refs, mode: str, real_j: int, n_workers: int):
    if mode == "fifo":
        shares_ref, qcount_ref, free_ref, u_ref, window_ref, *outs = refs
    else:
        shares_ref, qcount_ref, free_ref, u_ref, *outs = refs
    sel_ref, valid_ref, dany_ref, qout_ref, pops_ref = outs
    shares = shares_ref[...]                         # [BR, Jp]
    qcount = qcount_ref[...]                         # [BR, Jp] live counts
    free = free_ref[...] > 0                         # [BR, W]
    u = u_ref[...]                                   # [BR, W]
    pops = jnp.zeros_like(qcount)                    # ring advance so far
    jidx = jax.lax.broadcasted_iota(jnp.int32, qcount.shape, 1)
    widx = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    sel = jnp.zeros(u.shape, jnp.int32)
    valid_all = jnp.zeros(u.shape, jnp.int32)
    dany_all = jnp.zeros(u.shape, jnp.int32)
    if mode == "fifo":
        window = window_ref[...]                     # [BR, Jp, W]
        kidx = jax.lax.broadcasted_iota(jnp.int32, window.shape, 2)
    for w in range(n_workers):                       # static unroll
        demand = qcount > 0
        dany = jnp.any(demand, axis=-1, keepdims=True)               # [BR, 1]
        if mode == "themis":
            j_sel = weighted_draw(
                shares, demand, jax.lax.slice_in_dim(u, w, w + 1, axis=1),
                real_j, roll=pltpu.roll)                             # [BR, 1]
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


def block_call(rows: int, jp: int, w: int, *, mode: str, real_j: int,
               block_rows: int, interpret: bool):
    """The bare ``pallas_call`` over operands already padded to ``rows``
    (a multiple of ``block_rows``) × ``jp`` lanes, in the order
    ``(shares, qcount, free, u[, window])``.  :func:`tick_step_pallas`
    pads and picks ``block_rows`` by :func:`tick_step_grid`; a timing of
    the kernel alone may pick its own."""
    br = block_rows
    row = lambda i: (i, 0)
    wide = pl.BlockSpec((br, jp), row)
    narrow = pl.BlockSpec((br, w), row)
    in_specs = [wide, wide, narrow, narrow]
    if mode == "fifo":
        in_specs.append(pl.BlockSpec((br, jp, w), lambda i: (i, 0, 0)))
    return pl.pallas_call(
        functools.partial(_tick_step_kernel, mode=mode, real_j=real_j,
                          n_workers=w),
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=[narrow, narrow, narrow, wide, wide],
        out_shape=[jax.ShapeDtypeStruct((rows, w), jnp.int32)] * 3
                  + [jax.ShapeDtypeStruct((rows, jp), jnp.int32)] * 2,
        interpret=interpret,
        name=KERNEL_NAME,
    )


def _padded_call(mode: str, interpret: bool, shares, qcount, free, u,
                 window=None):
    """One invocation over ``[R, J]`` rows: pad, run, slice back."""
    r, j = qcount.shape
    w = u.shape[1]
    br, steps = tick_step_grid(r, j, w, mode)
    rp, jp = br * steps, _round_up(j, _LANES)
    pad = lambda x, dtype, *minor: jnp.pad(x.astype(dtype),
                                           [(0, rp - r), *minor])
    operands = [pad(shares, jnp.float32, (0, jp - j)),
                pad(qcount, jnp.int32, (0, jp - j)),
                pad(free, jnp.int32, (0, 0)), pad(u, jnp.float32, (0, 0))]
    if mode == "fifo":
        operands.append(pad(window, jnp.float32, (0, jp - j), (0, 0)))
    sel, valid, dany, qout, pops = block_call(
        rp, jp, w, mode=mode, real_j=j, block_rows=br,
        interpret=interpret)(*operands)
    return (sel[:r], valid[:r] > 0, dany[:r] > 0, qout[:r, :j],
            pops[:r, :j])


@functools.cache
def _folding_call(mode: str, interpret: bool):
    """The kernel's entry for one ``(mode, interpret)``, with a vmap rule
    that folds lanes into rows and calls itself once (an enclosing vmap
    folds again)."""

    @jax.custom_batching.custom_vmap
    def call(*operands):
        return _padded_call(mode, interpret, *operands)

    @call.def_vmap
    def fold(axis_size, in_batched, *operands):
        lanes = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
                 for x, b in zip(operands, in_batched)]
        outs = call(*[x.reshape((-1,) + x.shape[2:]) for x in lanes])
        outs = tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in outs)
        return outs, (True,) * len(outs)

    return call


def tick_step_pallas(shares: jnp.ndarray, qcount: jnp.ndarray,
                     window, free: jnp.ndarray, u: jnp.ndarray,
                     *, mode: str = "themis", interpret: bool = True):
    """shares, qcount: [S, J]; window: [S, J, W] (fifo mode only, else
    ignored and may be None); free, u: [S, W].

    Returns ``(sel i32[S,W], valid bool[S,W], demand_any bool[S,W],
    qcount_out i32[S,J], pops i32[S,J])`` — see ref.py for semantics.
    J is padded to the 128-lane width and the rows are blocked over the
    grid by :func:`tick_step_grid`; under ``jax.vmap`` the lanes fold into
    the rows of one invocation.  ``interpret=True`` runs the body on CPU
    (validation mode).
    """
    check_mode(mode, window)
    operands = (shares, qcount, free, u)
    if mode == "fifo":
        operands += (window,)
    return _folding_call(mode, interpret)(*operands)
