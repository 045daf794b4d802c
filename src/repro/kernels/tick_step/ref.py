"""Pure-jnp oracle for the fused tick-step kernel.

One call answers the whole worker phase of an engine tick: the W workers'
sequential select -> pop -> ring-head advance, exactly as
``repro.core.engine.make_tick``'s ``lax.scan`` performs it — same op
sequence per draw, so the oracle (and therefore the Pallas kernel held to
it) is bit-identical to the legacy scan.

Inputs are plain arrays so both planes can call it:

    shares  f32[S, J]   per-tick share table (themis mode)
    qcount  i32[S, J]   queued requests per (server, job) at tick start
    window  f32[S, J, W] next W ring arrival stamps per (server, job)
                        (window[s, j, k] = arr_time[s, j, (head + k) % cap]);
                        fifo mode only — themis mode reads none, and the
                        caller may pass None
    free    bool[S, W]  worker is free this tick
    u       f32[S, W]   per-worker uniform draws (PRNG stream precomputed
                        by the caller — stream identity is the caller's job)

Returns ``(sel, valid, demand_any, qcount_out, pops)``:

    sel        i32[S, W]  selected job per worker (-1 = idle draw)
    valid      bool[S, W] the pop actually happened (worker free & sel >= 0)
    demand_any bool[S, W] any queue was non-empty when worker w drew
    qcount_out i32[S, J]  queue counts after all pops
    pops       i32[S, J]  pops per (server, job) — the ring-head advance
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..token_select.ref import token_select_ref

#: In-kernel select modes: the statistical-token weighted draw (themis) and
#: the earliest-queued-arrival draw (fifo).
MODES = ("themis", "fifo")


def _fifo_pick(head_time: jnp.ndarray, demand: jnp.ndarray) -> jnp.ndarray:
    """Earliest queued arrival across jobs; -1 when all queues are empty
    (same ops as ``repro.core.baselines.fifo_select``)."""
    j = jnp.argmin(head_time, axis=-1).astype(jnp.int32)
    return jnp.where(demand.any(axis=-1), j, -1)


def check_mode(mode: str, window) -> None:
    """Fail loudly on an unknown mode, and on fifo mode without its
    window."""
    if mode not in MODES:
        raise ValueError(f"unknown tick-step mode {mode!r}; one of {MODES}")
    if mode == "fifo" and window is None:
        raise ValueError("fifo mode reads the [S, J, W] ring window; "
                         "got window=None")


def tick_step_ref(shares: jnp.ndarray, qcount: jnp.ndarray,
                  window, free: jnp.ndarray, u: jnp.ndarray,
                  mode: str = "themis"):
    check_mode(mode, window)
    s_, j_ = qcount.shape
    w_ = u.shape[1]
    pops = jnp.zeros_like(qcount)
    q = qcount
    sel_cols, valid_cols, dany_cols = [], [], []
    for w in range(w_):
        demand = q > 0
        if mode == "themis":
            j_sel = token_select_ref(shares, q, u[:, w:w + 1])[:, 0]
        else:
            ht = jnp.take_along_axis(window, pops[..., None], axis=-1)[..., 0]
            ht = jnp.where(demand, ht, jnp.inf)
            j_sel = _fifo_pick(ht, demand)
        valid = free[:, w] & (j_sel >= 0)
        j_safe = jnp.maximum(j_sel, 0)
        onehot = (jax.nn.one_hot(j_safe, j_, dtype=jnp.int32)
                  * valid[:, None].astype(jnp.int32))
        q = q - onehot
        pops = pops + onehot
        sel_cols.append(j_sel)
        valid_cols.append(valid)
        dany_cols.append(demand.any(axis=-1))
    return (jnp.stack(sel_cols, axis=1), jnp.stack(valid_cols, axis=1),
            jnp.stack(dany_cols, axis=1), q, pops)
