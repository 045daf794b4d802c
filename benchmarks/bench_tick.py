"""kern section: ref-vs-fused tick worker phase + roofline budget.

The engine's legacy worker phase is a W-step ``lax.scan`` — one weighted
draw, pop, and ring advance per step.  The fused tick-step op
(:mod:`repro.kernels.tick_step`) answers all W draws in one invocation
(Pallas kernel on TPU, the vectorized jnp oracle elsewhere — bit-identical
either way).  This section times both at engine geometry across the
``max_jobs`` ladder and reports:

    kern_tick_ref_j{J}        legacy scan worker phase, us/tick
    kern_tick_fused_j{J}      fused tick-step, us/tick
    kern_tick_speedup_j{J}    ref/fused ratio — the gated perf row
    kern_tick_budget_us_j{J}  roofline-derived per-tick budget (ungated;
                              repro.roofline.analysis.tick_step_roofline),
                              emitted on a TPU only: peaks are keyed by the
                              device kind, and a CPU has no entry

``BENCH_KERN_ITERS`` shrinks the timing loop for CI smoke.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels.tick_step import tick_step
from repro.kernels.token_select.ref import token_select_ref
from repro.roofline.analysis import tick_step_roofline

from .bench_kernels import _time

#: Engine geometry the ladder is timed at (servers x workers; J varies).
N_SERVERS = 8
N_WORKERS = 8
LADDER = (16, 256, 1024)


@functools.partial(jax.jit, static_argnames=("mode",))
def _scan_phase(shares, qcount, window, free, u, mode: str = "themis"):
    """The legacy worker phase: one draw per ``lax.scan`` step, the op
    sequence of ``repro.core.engine.make_tick``'s ``worker_body`` reduced to
    its queue updates (select -> pop -> ring-head advance)."""
    j_ = qcount.shape[1]
    w_ = u.shape[1]

    def body(carry, w):
        q, pops = carry
        demand = q > 0
        if mode == "themis":
            j_sel = token_select_ref(
                shares, q, jax.lax.dynamic_slice_in_dim(u, w, 1, axis=1))[:, 0]
        else:
            ht = jnp.take_along_axis(window, pops[..., None], axis=-1)[..., 0]
            ht = jnp.where(demand, ht, jnp.inf)
            j_sel = jnp.where(demand.any(axis=-1),
                              jnp.argmin(ht, axis=-1).astype(jnp.int32), -1)
        valid = jax.lax.dynamic_slice_in_dim(free, w, 1, axis=1)[:, 0] & (j_sel >= 0)
        onehot = (jax.nn.one_hot(jnp.maximum(j_sel, 0), j_, dtype=jnp.int32)
                  * valid[:, None].astype(jnp.int32))
        return (q - onehot, pops + onehot), j_sel

    (q, pops), sel = jax.lax.scan(
        body, (qcount, jnp.zeros_like(qcount)), jnp.arange(w_))
    return sel, q, pops


def _inputs(j: int):
    ks = jax.random.split(jax.random.PRNGKey(42), 5)
    shares = jnp.abs(jax.random.normal(ks[0], (N_SERVERS, j)))
    qcount = jax.random.randint(ks[1], (N_SERVERS, j), 0, 4)
    window = jnp.cumsum(
        jax.random.uniform(ks[2], (N_SERVERS, j, N_WORKERS)), axis=-1)
    free = jax.random.uniform(ks[3], (N_SERVERS, N_WORKERS)) < 0.9
    u = jax.random.uniform(ks[4], (N_SERVERS, N_WORKERS))
    return shares, qcount, window, free, u


def run_kern() -> list[tuple]:
    iters = int(os.environ.get("BENCH_KERN_ITERS", "30"))
    rows = []
    fused = jax.jit(functools.partial(tick_step, mode="themis", impl="auto"))
    on_tpu = jax.default_backend() == "tpu"
    for j in LADDER:
        args = _inputs(j)
        ref_us = _time(_scan_phase, *args, iters=iters, warmup=2)
        fused_us = _time(fused, *args, iters=iters, warmup=2)
        speedup = ref_us / fused_us if fused_us else 0.0
        rows.append((f"kern_tick_ref_j{j}", f"{ref_us:.1f}",
                     f"{ref_us:.1f} us/tick ({N_WORKERS}-step scan, "
                     f"{N_SERVERS}srv)"))
        rows.append((f"kern_tick_fused_j{j}", f"{fused_us:.1f}",
                     f"{fused_us:.1f} us/tick (fused tick-step, auto impl)"))
        rows.append((f"kern_tick_speedup_j{j}", "",
                     f"{speedup:.2f}x ref/fused"))
        if not on_tpu:
            continue
        roof = tick_step_roofline(N_SERVERS, j, N_WORKERS,
                                  device_kind=jax.devices()[0].device_kind)
        rows.append((f"kern_tick_budget_us_j{j}", "",
                     f"{roof['budget_us']:.3f} us roofline "
                     f"({roof['bound']}-bound, "
                     f"{roof['intensity_flops_per_byte']:.1f} flop/B)"))
    return rows
