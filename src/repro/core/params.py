"""Scheduler-owned parameter schemas — frozen dataclasses that are also pytrees.

Every entry in the :mod:`repro.core.scheduler` registry declares its knobs as
a frozen dataclass here.  The contract per schema:

  * **defaults** — instantiating with no arguments reproduces the calibrated
    behavior the benchmarks are pinned to (see ``benchmarks/calibrate.py``
    for how the adaptbf/plan defaults were chosen);
  * **validation** — ``__post_init__`` raises ``ValueError`` on out-of-range
    *concrete* values, so a typo fails at construction, not as a silent NaN
    40 s into a jitted scan.  Traced or batched values skip validation — they
    were validated when their concrete grid points were built;
  * **pytree registration** — every schema is registered with JAX
    (:func:`jax.tree_util.register_dataclass`): numeric knobs are *leaves*,
    threaded through the engine as runtime arguments, while structural knobs
    (``mu_ticks``, which changes the trace) stay static metadata.

The pytree split is what makes one-compile parameter sweeps work: the engine
traces its tick once with the numeric knobs as abstract scalars, and
``jax.vmap`` batches P grid points × K seeds through that single executable
(:func:`repro.core.engine.run_batch` with ``params_points``, or
:meth:`repro.api.Experiment.sweep`).  Changing a numeric knob between runs
re-uses the trace; changing ``mu_ticks`` recompiles, which is why
:func:`stack_params` refuses grids that mix μ cadences.

Resolution (``SchedulerParams.resolve``): an explicit
``EngineConfig.scheduler_params`` wins; otherwise the schema defaults.  The
legacy flat ``gift_*``/``tbf_*``/``adaptbf_*``/``plan_*`` ``EngineConfig``
knobs and their deprecation shim were removed this release (they warned for
one release; see the README migration table in the git history).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import FrozenSet, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: μ cadence every interval scheduler shares by default (ticks); §5.4 finds
#: μ = 0.5 s (500 ticks at dt=1 ms) works best on this substrate.
DEFAULT_MU_TICKS = 500

#: Structural fields: they change the *trace* (scan cadence / scan length),
#: not just the numbers flowing through it, so they are pytree metadata,
#: never leaves.  ``sa_steps``/``sa_restarts`` set the simulated-annealing
#: scan length in the batch plane (:mod:`repro.batch.plan`), exactly as
#: ``mu_ticks`` sets the interval cadence in the serving plane.
STATIC_FIELDS: FrozenSet[str] = frozenset({"mu_ticks", "sa_steps",
                                           "sa_restarts"})


def _require(cond, msg: str) -> None:
    if not cond:
        raise ValueError(msg)


def _abstract_values(p) -> bool:
    """True when any field came from pytree plumbing rather than a concrete
    construction: a JAX tracer (jit argument / vmap lane), a non-scalar
    array (a stacked sweep grid), the ``ArgInfo`` leaves ``jit(...).lower``
    rebuilds the arguments from, or the bare ``object()`` sentinels JAX
    threads through ``unflatten`` during tree transposition.  Validation
    skips those — they were validated when their concrete grid points were
    built — but still runs (and raises eagerly, e.g. on a string) for every
    genuinely concrete value."""
    for f in dataclasses.fields(p):
        v = getattr(p, f.name)
        if (isinstance(v, (jax.core.Tracer, jax.stages.ArgInfo))
                or type(v) is object):
            return True
        if getattr(v, "ndim", 0) != 0:
            return True
    return False


def schema(cls):
    """Class decorator: freeze the dataclass and register it with JAX.

    Numeric knobs become pytree leaves (traced at run time); the structural
    :data:`STATIC_FIELDS` stay metadata, so two params objects with different
    ``mu_ticks`` have different treedefs and can never be silently batched
    into one trace.
    """
    cls = dataclasses.dataclass(frozen=True)(cls)
    names = [f.name for f in dataclasses.fields(cls)]
    jax.tree_util.register_dataclass(
        cls,
        data_fields=[n for n in names if n not in STATIC_FIELDS],
        meta_fields=[n for n in names if n in STATIC_FIELDS])
    return cls


@schema
class SchedulerParams:
    """Base schema: no knobs. Schedulers with no tunables use it directly
    via a trivial subclass, so ``available_schedulers()`` can promise every
    entry exposes a schema with defaults."""

    def __post_init__(self):
        if not _abstract_values(self):
            self._validate()

    def _validate(self) -> None:
        """Eager range checks on concrete values; subclasses extend."""

    @classmethod
    def numeric_fields(cls) -> List[str]:
        """Field names that are pytree leaves (sweepable in one compile)."""
        return [f.name for f in dataclasses.fields(cls)
                if f.name not in STATIC_FIELDS]

    @classmethod
    def resolve(cls, cfg) -> "SchedulerParams":
        """Explicit ``cfg.scheduler_params`` wins; else the schema defaults.

        The type check is exact, not ``isinstance``: schemas share bases
        (``_BucketParams``, ``_IntervalParams``), and accepting a sibling or
        subclass schema for the wrong scheduler would silently run it with
        another algorithm's calibrated values (and stamp the wrong params
        hash into benchmark artifacts).
        """
        p = getattr(cfg, "scheduler_params", None)
        if p is None:
            return cls()
        if type(p) is not cls:
            raise TypeError(
                f"scheduler_params is {type(p).__name__}, but the configured "
                f"scheduler expects exactly {cls.__name__}")
        return p

    def params_hash(self) -> str:
        """Stable short hash of (schema type, every field value) — stamped
        into BENCH_*.json so perf-trend points are attributable to configs."""
        doc = {"schema": type(self).__name__}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            doc[f.name] = v.item() if hasattr(v, "item") else v
        blob = json.dumps(doc, sort_keys=True, default=repr).encode()
        return hashlib.sha256(blob).hexdigest()[:12]


def stack_params(points: Sequence[SchedulerParams]) -> SchedulerParams:
    """Stack P concrete grid points into one batched params pytree.

    Every numeric leaf gains a leading ``P`` axis (f32), ready for
    ``jax.vmap``; all points must be the *same* schema with the *same*
    structural fields (``mu_ticks``), because those are baked into the trace
    — a grid that varies μ needs one compile per μ group.
    """
    points = list(points)
    if not points:
        raise ValueError("stack_params needs at least one grid point")
    p0 = points[0]
    for i, p in enumerate(points):
        if type(p) is not type(p0):
            raise TypeError(
                f"grid point {i} is {type(p).__name__}, expected "
                f"{type(p0).__name__} — a sweep grid holds one schema")
        for name in STATIC_FIELDS:
            if hasattr(p0, name) and getattr(p, name) != getattr(p0, name):
                raise ValueError(
                    f"grid point {i} has {name}={getattr(p, name)} != "
                    f"{getattr(p0, name)}: structural fields are static in "
                    "the trace; sweep them as separate compiles")
    return jax.tree_util.tree_map(
        lambda *xs: jnp.asarray(np.asarray(xs, np.float32)), *points)


@schema
class ThemisParams(SchedulerParams):
    """Statistical tokens have no per-scheduler tunables: the policy chain,
    λ cadence (``EngineConfig.sync_ticks``) and Sinkhorn iteration count are
    engine/policy-level concerns shared with the sync subsystem."""


@schema
class FifoParams(SchedulerParams):
    """Arrival order needs no knobs."""


@schema
class _IntervalParams(SchedulerParams):
    """Shared μ cadence for every interval scheduler (budget resets, borrow
    exchanges, replanning).  Structural: it sets the ``lax.cond`` cadence in
    the engine scan, so it is pytree metadata, not a traced leaf."""

    mu_ticks: int = DEFAULT_MU_TICKS

    def _validate(self):
        super()._validate()
        _require(self.mu_ticks > 0, f"mu_ticks must be > 0, got {self.mu_ticks}")


@schema
class GiftParams(_IntervalParams):
    """GIFT (FAST'20): BSIP equal-share interval budgets + throttle-and-reward
    coupons; ``ctrl_overhead_s`` models the BSIP pause/resume + progress-sync
    cost per request."""

    coupon_frac: float = 0.5
    ctrl_overhead_s: float = 5e-4

    def _validate(self):
        super()._validate()
        _require((0.0 <= self.coupon_frac) & (self.coupon_frac <= 1.0),
                 f"coupon_frac must be in [0, 1], got {self.coupon_frac}")
        _require(self.ctrl_overhead_s >= 0.0,
                 f"ctrl_overhead_s must be >= 0, got {self.ctrl_overhead_s}")


@schema
class _BucketParams(_IntervalParams):
    """Shared token-bucket base: TBF and AdapTBF deliberately share the
    per-job ``rate``, so comparing the two isolates exactly what the
    borrowing mechanism buys.  Not a parent/child relationship — each
    scheduler's schema carries only its own knobs, so params hashes never
    drag inert fields along."""

    rate: float = 0.0
    burst_s: float = 0.25
    ctrl_overhead_s: float = 5.5e-4

    def _validate(self):
        super()._validate()
        _require(self.rate >= 0.0, f"rate must be >= 0, got {self.rate}")
        _require(self.burst_s >= 0.0,
                 f"burst_s must be >= 0, got {self.burst_s}")
        _require(self.ctrl_overhead_s >= 0.0,
                 f"ctrl_overhead_s must be >= 0, got {self.ctrl_overhead_s}")

    def rate_eff(self, cfg):
        """Effective per-job rate: configured, or an equal split of server
        bandwidth over job slots when left at 0.  ``jnp.where`` (not ``if``)
        because ``rate`` may be a traced sweep leaf."""
        return jnp.where(self.rate > 0, self.rate,
                         cfg.server_bw / cfg.max_jobs)


@schema
class TbfParams(_BucketParams):
    """TBF (SC'17): classful token buckets at user-supplied ``rate`` (bytes/s
    per job; 0 means ``server_bw / max_jobs``), HTC hard accounting and PSSB
    conservative spare sharing."""

    headroom: float = 0.8

    def _validate(self):
        super()._validate()
        _require((0.0 <= self.headroom) & (self.headroom <= 1.0),
                 f"headroom must be in [0, 1], got {self.headroom}")


@schema
class AdaptbfParams(_BucketParams):
    """AdapTBF (arXiv:2602.22409): TBF's buckets plus a per-μ decentralized
    borrow exchange.  Shares the bucket base's ``rate`` with calibrated
    AdapTBF depth/overhead defaults; ``repay`` is the per-μ repayment decay
    on the borrowed-token ledger.

    ``burst_s``/``repay`` defaults come from ``benchmarks/calibrate.py``
    (12 s × 4 seeds, fig12 contention): the least-mechanism point on the
    near-work-conserving Jain plateau — burst_s=2.0 is interior (1.0
    throttles to 20.9/21.4 GB/s, 4.0 erodes Jain to 0.999), repay is flat on
    this workload so the gentlest decay wins the tie.  Operating point:
    21.42 GB/s sustained, Jain 0.9999.

    ``donate`` enables the *fleet-level* exchange on top of the per-server
    one: after each server matches its own donors and borrowers, a fraction
    ``donate`` of every job's remaining surplus is pooled **across all
    servers** and waterfilled over the global deficits
    (:func:`repro.core.baselines.adaptbf_cross_donate`) — in a sharded
    engine that pool spans device shards (repayment stays shard-local).
    The default 0.0 keeps the exchange strictly per-server, bitwise
    identical to the pre-fleet behavior.
    """

    burst_s: float = 2.0
    ctrl_overhead_s: float = 1e-4    # no rule engine: local bucket ops only
    repay: float = 0.1
    donate: float = 0.0

    def _validate(self):
        super()._validate()
        _require((0.0 <= self.repay) & (self.repay <= 1.0),
                 f"repay must be in [0, 1], got {self.repay}")
        _require((0.0 <= self.donate) & (self.donate <= 1.0),
                 f"donate must be in [0, 1], got {self.donate}")


@schema
class PlanParams(_IntervalParams):
    """Plan-based lookahead (arXiv:2109.00082): per-μ EFT plan over a qcount
    EMA; ``ema_alpha`` is the history weight per μ.

    The ``ema_alpha`` default comes from ``benchmarks/calibrate.py``
    (12 s × 4 seeds, fig12 contention): the source paper's waiting-time
    objective — minimize the later-arriving job's slowdown vs solo — is a
    plateau for α ∈ [0.2, 0.7] (slowdown 1.936–1.944; α=0.1 lags at 1.970,
    α=0.9 chases noise at 2.069); the smoothest estimator on the plateau
    wins the tie.
    """

    ema_alpha: float = 0.2
    ctrl_overhead_s: float = 2e-4

    def _validate(self):
        super()._validate()
        _require((0.0 < self.ema_alpha) & (self.ema_alpha <= 1.0),
                 f"ema_alpha must be in (0, 1], got {self.ema_alpha}")
        _require(self.ctrl_overhead_s >= 0.0,
                 f"ctrl_overhead_s must be >= 0, got {self.ctrl_overhead_s}")


@schema
class PlanOptParams(SchedulerParams):
    """Plan-*optimization* knobs for the batch plane (arXiv:2109.00082 §4 /
    the 2111.10200 thesis): simulated annealing over job orderings inside a
    lookahead window, evaluated with the reservation-aware list scheduler
    (:func:`repro.batch.sim.schedule_order`).

    Not a serving-plane scheduler schema — it parameterizes
    :func:`repro.batch.plan.plan_schedule` and travels through the same
    pytree/params-hash machinery so annealing sweeps are attributable and
    workspace-cacheable.  ``sa_steps``/``sa_restarts`` set the SA scan
    length/width, so they are structural (:data:`STATIC_FIELDS`): changing
    them recompiles; ``t0_s``/``cooling`` are traced leaves.  ``t0_s`` is
    the initial Metropolis temperature in *seconds of mean waiting time*
    (the objective's unit); ``lookahead_s`` bounds the planning window —
    jobs submitted beyond it keep their arrival order at the plan's tail.
    """

    sa_steps: int = 400
    sa_restarts: int = 2
    t0_s: float = 600.0
    cooling: float = 0.985
    lookahead_s: float = 1e9

    def _validate(self):
        super()._validate()
        _require(self.sa_steps >= 1,
                 f"sa_steps must be >= 1, got {self.sa_steps}")
        _require(self.sa_restarts >= 1,
                 f"sa_restarts must be >= 1, got {self.sa_restarts}")
        _require(self.t0_s > 0.0, f"t0_s must be > 0, got {self.t0_s}")
        _require((0.0 < self.cooling) & (self.cooling <= 1.0),
                 f"cooling must be in (0, 1], got {self.cooling}")
        _require(self.lookahead_s > 0.0,
                 f"lookahead_s must be > 0, got {self.lookahead_s}")
