"""λ-delayed global fairness (paper §3.1, Fig. 5).

With files striped across disjoint server subsets, each server initially sees
only its local jobs and allocates tokens from that view, which is globally
unfair (Fig. 5: a job striped over two servers gets 0.66 of each instead of
0.5).  Every λ the controllers all-gather the job status tables, and each
server re-derives its token segments from the *global* view.

The paper states the adjustment ("every server adjusts the statistical token
of Job 1") but not the algorithm.  We solve the implied allocation problem —
per-server segment matrix ``A[s, j] >= 0`` with row sums 1 (each server's
cycles fully assigned), column sums proportional to the global policy shares,
and support restricted to servers where the job actually has I/O — by
iterative proportional fitting (Sinkhorn).  On the paper's worked example
(jobs sized 16:8:8, job 1 on both servers, jobs 2/3 disjoint) it converges to
exactly the paper's fixed point: job 1 gets 0.5 on each server.

When the marginals are infeasible (e.g. a job entitled to more than the
servers it touches can supply), Sinkhorn converges to the closest achievable
allocation — the spare capacity is recycled to co-located jobs, which is
precisely opportunity fairness at the cross-server level.

Two transports are provided:
  * :func:`sync_segments` — pure jnp, single array holding all servers
    (the discrete-event engine path).
  * :func:`make_sharded_sync` — ``shard_map`` + ``jax.lax`` all-gather over a
    named mesh axis, the production path where each server (device) owns its
    row of the demand matrix.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .policy import Policy, compute_job_shares
from .job_table import JobTable


def sinkhorn_balance(
    support: jnp.ndarray,        # f32[S, J]  1.0 where job j has I/O on server s
    col_targets: jnp.ndarray,    # f32[J]     global job shares (sum <= 1)
    n_iters: int = 32,
) -> jnp.ndarray:
    """Balance per-server segments to match global shares on a support set.

    Row targets are each server's full capacity (1/S of the system each);
    column targets are the policy's global shares.  Returns A with rows
    summing to 1 (over live columns) — each server's segment table.
    """
    s = support.shape[0]
    row_t = jnp.full((s,), 1.0 / s, dtype=jnp.float32)
    col_t = col_targets.astype(jnp.float32)
    col_live = (support.sum(axis=0) > 0) & (col_t > 0)
    col_t = jnp.where(col_live, col_t, 0.0)
    tot = jnp.maximum(col_t.sum(), 1e-30)
    col_t = col_t / tot  # normalize over reachable jobs (opportunity recycle)

    a = support * col_t[None, :]

    def body(a, _):
        # column scaling
        csum = a.sum(axis=0)
        a = a * jnp.where(csum > 0, col_t / jnp.maximum(csum, 1e-30), 0.0)[None, :]
        # row scaling
        rsum = a.sum(axis=1, keepdims=True)
        a = a * jnp.where(rsum > 0, row_t[:, None] / jnp.maximum(rsum, 1e-30), 0.0)
        return a, None

    a, _ = jax.lax.scan(body, a, None, length=n_iters)
    # Express each row as that server's local segment table (sums to 1).
    rsum = a.sum(axis=1, keepdims=True)
    return jnp.where(rsum > 0, a / jnp.maximum(rsum, 1e-30), 0.0)


def global_shares(policy: Policy, table: JobTable, any_demand: jnp.ndarray) -> jnp.ndarray:
    """Global policy shares over jobs with demand anywhere (all-gathered view)."""
    return compute_job_shares(
        policy,
        active=table.active,
        user_id=table.user_id,
        group_id=table.group_id,
        size=table.size,
        priority=table.priority,
        demand=any_demand,
    )


def sync_segments(
    policy: Policy,
    table: JobTable,
    server_demand: jnp.ndarray,   # bool[S, J] per-server demand at sync time
    n_iters: int = 32,
) -> jnp.ndarray:
    """One λ-sync: merged table -> global shares -> balanced per-server segments."""
    any_demand = server_demand.any(axis=0)
    g = global_shares(policy, table, any_demand)
    return sinkhorn_balance(server_demand.astype(jnp.float32), g, n_iters=n_iters)


def local_segments(policy: Policy, table: JobTable, server_demand: jnp.ndarray) -> jnp.ndarray:
    """Per-server segments from the purely *local* view (pre-first-sync state)."""
    fn = functools.partial(
        compute_job_shares, policy,
        user_id=table.user_id, group_id=table.group_id,
        size=table.size, priority=table.priority,
    )
    return jax.vmap(lambda d: fn(active=table.active & d, demand=d))(server_demand)


def make_sharded_sync(policy: Policy, mesh, axis: str = "data") -> Callable:
    """Production transport: each device owns one server's demand row.

    Returns ``f(table, demand_row[S_local, J]) -> segments[S_local, J]`` where
    the all-gather over ``axis`` implements the paper's controller sync (UCX
    all-gather -> ``jax.lax.all_gather``).
    """
    def _local(table: JobTable, demand_row: jnp.ndarray) -> jnp.ndarray:
        full = jax.lax.all_gather(demand_row, axis_name=axis, tiled=True)  # [S, J]
        segs = sync_segments(policy, table, full)
        idx = jax.lax.axis_index(axis) * demand_row.shape[0]
        return jax.lax.dynamic_slice_in_dim(segs, idx, demand_row.shape[0], axis=0)

    return jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
