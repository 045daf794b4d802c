"""Sharing policies and the statistical-token transition-matrix chain (paper §3, Eq. 1).

A policy is an ordered list of *levels*. Each level names a sharing entity
(``group`` ⊐ ``user`` ⊐ ``job``) and a weight rule (``fair`` | ``size`` |
``priority``).  The paper's examples map to:

    job-fair              -> [job:fair]
    size-fair             -> [job:size]
    priority-fair         -> [job:priority]
    user-fair             -> [user:fair, job:fair]
    user-then-size-fair   -> [user:fair, job:size]
    group-then-user-fair  -> [group:fair, user:fair, job:fair]
    group-user-size-fair  -> [group:fair, user:fair, job:size]

Each level *i* induces a transition matrix ``T^i`` whose rows are the token
queues of level *i-1* and whose columns are the entities of level *i*; rows
sum to one and each column has exactly one non-zero entry (an entity belongs
to one parent).  The statistical token assignment is the chain product
``prod_i T^i`` (Eq. 1), giving one probability segment per job.

Opportunity fairness (§3 / §5.3.1) is implemented by recomputing the chain
with *demand-masked* entities: an entity with no queued I/O anywhere in its
subtree receives zero weight and its siblings absorb its share, so the system
is work-conserving at every level of the hierarchy.

Everything here is pure jnp over fixed-size slot arrays, so it can be jitted,
vmapped over servers, and run inside the discrete-event engine's `lax.scan`.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

ENTITIES = ("group", "user", "job")
WEIGHTS = ("fair", "size", "priority")
_ENTITY_RANK = {e: i for i, e in enumerate(ENTITIES)}


@dataclasses.dataclass(frozen=True)
class Level:
    entity: str
    weight: str = "fair"

    def __post_init__(self):
        if self.entity not in ENTITIES:
            raise ValueError(f"unknown entity {self.entity!r}; expected one of {ENTITIES}")
        if self.weight not in WEIGHTS:
            raise ValueError(f"unknown weight {self.weight!r}; expected one of {WEIGHTS}")


@dataclasses.dataclass(frozen=True)
class Policy:
    """A composite sharing policy: a strictly coarse-to-fine chain of levels.

    The final level must be ``job`` (requests belong to jobs). Construct via
    :func:`parse` / the named constructors rather than directly when possible.
    """

    levels: tuple[Level, ...]
    name: str = ""

    def __post_init__(self):
        if not self.levels:
            raise ValueError("policy needs at least one level")
        ranks = [_ENTITY_RANK[l.entity] for l in self.levels]
        if any(b <= a for a, b in zip(ranks, ranks[1:])):
            raise ValueError(f"levels must be strictly coarse-to-fine, got {self.levels}")
        if self.levels[-1].entity != "job":
            raise ValueError("final level must be 'job' (use Policy.parse to auto-append)")

    @property
    def depth(self) -> int:
        return len(self.levels)

    @staticmethod
    def parse(spec: str) -> "Policy":
        """Parse either a paper-style name or a ``entity:weight,...`` chain."""
        named = {
            "fifo": None,  # handled by the engine as a baseline, not a token policy
            "job-fair": "job:fair",
            "size-fair": "job:size",
            "priority-fair": "job:priority",
            "user-fair": "user:fair,job:fair",
            "group-fair": "group:fair,user:fair,job:fair",
            "user-then-job-fair": "user:fair,job:fair",
            "user-then-size-fair": "user:fair,job:size",
            "group-then-user-fair": "group:fair,user:fair,job:fair",
            "group-then-size-fair": "group:fair,job:size",
            "group-user-size-fair": "group:fair,user:fair,job:size",
        }
        chain = named.get(spec, spec)
        if chain is None:
            raise ValueError("'fifo' is a baseline scheduler, not a token policy")
        if spec not in named:
            # Not a known name: it must be a well-formed entity chain.  A
            # misspelled named policy ("user-fiar") must fail loudly here,
            # not fall through to a confusing chain-grammar error.
            tokens = [part.strip().partition(":")[0].strip()
                      for part in chain.split(",")]
            if not all(t in ENTITIES for t in tokens):
                known = ", ".join(sorted(k for k, v in named.items() if v))
                raise ValueError(
                    f"unknown policy {spec!r}. Known named policies: {known}. "
                    f"Or give an 'entity[:weight],...' chain with entities "
                    f"{ENTITIES} and weights {WEIGHTS}, "
                    f"e.g. 'group:fair,user:fair,job:size'.")
        levels = []
        for part in chain.split(","):
            entity, _, weight = part.strip().partition(":")
            levels.append(Level(entity, weight or "fair"))
        if levels[-1].entity != "job":
            levels.append(Level("job", "fair"))
        return Policy(tuple(levels), name=spec)


def job_fair() -> Policy:
    return Policy.parse("job-fair")


def size_fair() -> Policy:
    return Policy.parse("size-fair")


def user_fair() -> Policy:
    return Policy.parse("user-fair")


def priority_fair() -> Policy:
    return Policy.parse("priority-fair")


# ---------------------------------------------------------------------------
# Transition-matrix chain (Eq. 1)
# ---------------------------------------------------------------------------

def _entity_ids(entity: str, user_id: jnp.ndarray, group_id: jnp.ndarray) -> jnp.ndarray:
    n = user_id.shape[0]
    if entity == "job":
        return jnp.arange(n, dtype=jnp.int32)
    if entity == "user":
        return user_id.astype(jnp.int32)
    return group_id.astype(jnp.int32)


def _per_job_weight(weight: str, size: jnp.ndarray, priority: jnp.ndarray) -> jnp.ndarray:
    if weight == "fair":
        return jnp.ones_like(size, dtype=jnp.float32)
    if weight == "size":
        return size.astype(jnp.float32)
    return priority.astype(jnp.float32)


def transition_matrices(
    policy: Policy,
    *,
    active: jnp.ndarray,      # bool[J]  job slot is live (heartbeat, in table)
    user_id: jnp.ndarray,     # int32[J] in [0, J)
    group_id: jnp.ndarray,    # int32[J] in [0, J)
    size: jnp.ndarray,        # int32/float32[J] node count
    priority: jnp.ndarray,    # float32[J]
    demand: jnp.ndarray | None = None,  # bool[J] job has queued I/O (opportunity fairness)
) -> list[jnp.ndarray]:
    """Build the chain of transition matrices ``T^0 .. T^{N-1}`` (paper Fig. 4).

    Entities are indexed over ``J`` slots.  The job level is ``J`` wide; a
    group or user level has composite ids (``parent·J + raw``), so it is
    ``J`` times as wide as its parent: ``T^0`` is ``(1, J)``, and under
    ``group-then-user-fair`` ``T^1`` is ``(J, J²)`` and ``T^2`` is
    ``(J², J)`` (:func:`chain_elements` counts them).  Rows sum to one (or
    are all-zero for parents with no live descendants).
    """
    n = active.shape[0]
    mask = active.astype(bool)
    if demand is not None:
        mask = mask & demand.astype(bool)
    maskf = mask.astype(jnp.float32)

    mats: list[jnp.ndarray] = []
    # Parent ids of each *job* at the previous level; the virtual root is
    # level -1.  Mid-level entity ids are *composite* (parent_id * n + raw
    # id): sharing entities are scoped to their parent (paper §3: "the
    # sharing percentage is applied within the local sharing entity scope"),
    # so e.g. user 7 under group 0 and user 7 under group 1 are distinct
    # sharing entities — this also guarantees the single-parent column
    # invariant the chain product relies on.
    prev_ids = jnp.zeros((n,), dtype=jnp.int32)
    prev_dim = 1
    for level in policy.levels:
        raw = _entity_ids(level.entity, user_id, group_id)
        if level.entity == "job":
            cid = raw          # jobs are globally unique already
            dim = n
        else:
            cid = prev_ids * n + raw
            dim = prev_dim * n
        w_job = _per_job_weight(level.weight, size, priority) * maskf
        if level.weight == "fair":
            # fair: each live entity weighs 1, regardless of member count
            w_child = (jax.ops.segment_sum(maskf, cid, num_segments=dim) > 0
                       ).astype(jnp.float32)
        else:
            w_child = jax.ops.segment_sum(w_job, cid, num_segments=dim)
        child_live = jax.ops.segment_sum(maskf, cid, num_segments=dim) > 0
        # Parent of each child entity: unique by composite construction.
        parent_of_child = jax.ops.segment_max(
            jnp.where(mask, prev_ids, -1), cid, num_segments=dim
        )
        cols = jnp.where(child_live, w_child, 0.0)  # (dim,)
        tm = (parent_of_child[None, :]
              == jnp.arange(prev_dim, dtype=jnp.int32)[:, None])
        tm = tm.astype(jnp.float32) * cols[None, :]
        row_sum = tm.sum(axis=1, keepdims=True)
        tm = jnp.where(row_sum > 0, tm / jnp.maximum(row_sum, 1e-30), 0.0)
        mats.append(tm)
        prev_ids = cid
        prev_dim = dim
    return mats


def compute_job_shares(
    policy: Policy,
    *,
    active: jnp.ndarray,
    user_id: jnp.ndarray,
    group_id: jnp.ndarray,
    size: jnp.ndarray,
    priority: jnp.ndarray,
    demand: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Evaluate Eq. 1: the chain product of the transition matrices.

    Returns ``f32[J]`` job shares that sum to 1 over live (and, if ``demand``
    is given, demanded) jobs — or all zeros when nothing is live.  Each
    product (over mid levels ``J²`` wide; see :func:`transition_matrices`)
    is taken at ``Precision.HIGHEST``: at the TPU's default precision an
    f32 matmul may round its operands to bfloat16, which would move
    non-dyadic shares such as 1/3.
    """
    mats = transition_matrices(
        policy, active=active, user_id=user_id, group_id=group_id,
        size=size, priority=priority, demand=demand,
    )
    vec = jnp.ones((1, 1), dtype=jnp.float32)
    for tm in mats:
        vec = jnp.matmul(vec, tm, precision=jax.lax.Precision.HIGHEST)
    return vec[0]


@functools.lru_cache(maxsize=None)
def chain_elements(policy: Policy, n: int) -> int:
    """Elements of the transition matrices :func:`transition_matrices`
    builds over ``n`` slots, ``sum_l rows_l * cols_l``, read off the shapes
    it returns (traced abstractly once per policy and ``n``; nothing is
    computed)."""
    slot = jax.ShapeDtypeStruct((n,), jnp.int32)
    mats = jax.eval_shape(
        lambda a, u, g, s, p: transition_matrices(
            policy, active=a, user_id=u, group_id=g, size=s, priority=p),
        jax.ShapeDtypeStruct((n,), jnp.bool_), slot, slot, slot,
        jax.ShapeDtypeStruct((n,), jnp.float32))
    return sum(math.prod(m.shape) for m in mats)


def compute_job_shares_from_table(policy: Policy, table, demand=None) -> jnp.ndarray:
    """Convenience wrapper over a :class:`repro.core.job_table.JobTable`."""
    return compute_job_shares(
        policy,
        active=table.active,
        user_id=table.user_id,
        group_id=table.group_id,
        size=table.size,
        priority=table.priority,
        demand=demand,
    )
