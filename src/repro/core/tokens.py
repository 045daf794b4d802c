"""Statistical tokens: segment tables, opportunity renormalization, worker draws.

The paper's workers draw ``u ~ U[0,1)`` and serve the job whose probability
segment contains ``u`` (§3).  On TPU/JAX the lock-free queue pop becomes a
branchless masked weighted choice: mask shares by queue occupancy, renormalize
(opportunity fairness / token recycling), prefix-sum, and binary-search the
draw.  :func:`select_job` routes through the
``repro.kernels.token_select.ops.token_select`` dispatcher — the pure-jnp
oracle on CPU (bit-exact with the historical in-module math), the fused
Pallas kernel on TPU (or anywhere with ``impl="pallas"``, interpret-mode off
TPU) — so the engine, the burst-buffer service, and the serving engine all
draw through one seam.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.prefix import prefix_sum
from repro.kernels.token_select.ops import token_select


def opportunity_renorm(shares: jnp.ndarray, demand: jnp.ndarray) -> jnp.ndarray:
    """Recycle tokens of idle jobs: renormalize shares over demanded jobs.

    Flat renormalization — used per-tick between λ-syncs. Hierarchical
    (within-scope-first) redistribution is obtained by recomputing the policy
    chain with a demand mask (see :func:`repro.core.policy.compute_job_shares`).
    """
    masked = shares * demand.astype(shares.dtype)
    total = masked.sum(axis=-1, keepdims=True)
    return jnp.where(total > 0, masked / jnp.maximum(total, 1e-30), 0.0)


def shares_have_mass(shares: jnp.ndarray, demand: jnp.ndarray) -> jnp.ndarray:
    """bool[...]: does any *demanded* job carry positive share mass?

    Schedulers use this to decide whether a share table can drive a draw or a
    fallback (e.g. the local policy chain) is needed for this tick.
    """
    return opportunity_renorm(shares, demand).sum(axis=-1) > 0


def segments(shares: jnp.ndarray) -> jnp.ndarray:
    """Cumulative segment boundaries over [0, 1]; last entry == total mass.

    The same log-step prefix sum the token kernels and their oracle run
    (:func:`repro.kernels.prefix.prefix_sum`), so every segment table is
    added in one order on every backend."""
    return prefix_sum(jnp.asarray(shares))


def select_job(shares: jnp.ndarray, demand: jnp.ndarray, u: jnp.ndarray,
               impl: str = "auto") -> jnp.ndarray:
    """One worker token draw: pick the job whose segment contains ``u``.

    shares: f32[..., J] (need not be normalized), demand: bool[..., J],
    u: f32[...] in [0,1).  Returns int32[...] job index, or -1 when no job has
    demand (worker idles — opportunity fairness never blocks on idle slots).

    ``impl`` selects the fused draw implementation (see
    :mod:`repro.kernels.token_select.ops`): ``auto`` (Pallas on TPU, jnp
    oracle elsewhere), ``ref``, or ``pallas``.  Both implementations run the
    same op sequence, so the draw is bit-identical across them on CPU.
    """
    shares = jnp.asarray(shares)
    demand = jnp.asarray(demand)
    u = jnp.asarray(u)
    j = shares.shape[-1]
    batch = shares.shape[:-1]
    idx = token_select(
        shares.reshape((-1, j)),
        demand.reshape((-1, j)).astype(jnp.int32),
        u.reshape((-1, 1)).astype(jnp.float32),
        impl=impl)[:, 0]
    return idx.reshape(batch)


def draw_uniform(key: jax.Array, shape) -> jnp.ndarray:
    return jax.random.uniform(key, shape, dtype=jnp.float32)


def expected_selection_freq(shares: jnp.ndarray, demand: jnp.ndarray) -> jnp.ndarray:
    """The stationary pick distribution given persistent demand — test helper."""
    return opportunity_renorm(shares, demand)
