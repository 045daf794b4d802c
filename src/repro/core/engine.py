"""Vectorized discrete-event burst-buffer engine (paper §5 testbed, in JAX).

Models a remote-shared burst buffer: ``S`` servers, each with ``W`` workers
sharing the server's bandwidth, serving phased client populations.  All
state lives in fixed-shape jnp arrays; one simulated tick is a pure function
and the whole run is a single ``jax.lax.scan`` — the entire testbed
jit-compiles.

Workloads are **scenarios**: each job is a sequence of phases held in
fixed-shape ``[J, P]`` arrays (start/end/request/think per phase, padded
with inactive rows), and the tick step selects each job's current phase
with a mask — so bursty checkpoint/restart loops, ramps, and idle windows
(the patterns behind the paper's opportunity-fairness and §5.5 application
claims) express without leaving the one-compile jit/vmap path.  A flat
single-window spec lowers to ``P = 1`` and runs bit-identically to the
pre-scenario engine.  Each phase arrives **closed-loop** (the paper's
benchmark: write, wait, think, repeat), on a **fixed interval** (every
``interval_s`` all client processes issue one request — a synchronized
checkpoint burst), or **Poisson** (per-process rate ``rate_hz``, drawn from
the run's PRNG seed) — the open-loop modes decouple arrival timing from
completion.

Scheduling is pluggable: ``EngineConfig.scheduler`` names an entry in the
:mod:`repro.core.scheduler` registry (``available_schedulers()`` — ``themis``,
``fifo``, ``gift``, ``tbf``, ``adaptbf``, ``plan`` ship with the repo) and
the engine only ever talks to the Scheduler interface
— ``pre_tick`` for bookkeeping, ``tick_shares`` for the per-tick share table,
``select`` for the per-worker draw, ``charge`` to debit accounts.  The same
objects drive the functional plane (:mod:`repro.bb.service`), so both planes
provably run one scheduling algorithm.

Scheduler *parameters* are runtime data, not trace constants: the resolved
params schema (:mod:`repro.core.params`) is a pytree whose numeric knobs are
scalar leaves passed into the jitted scan as arguments.  The trace never
depends on their values, which is what lets :func:`run_batch` with
``params_points`` vmap P grid points × K seeds through ONE compile — the
backbone of calibration sweeps (``benchmarks/calibrate.py``) that used to
pay one compile per grid point.  (Sequential :func:`run` calls still build
a fresh jit each, so batching over ``params_points`` — not looping — is how
the single compile is realized.)  Only structural fields (``mu_ticks``)
stay static.

Time-accounting note: workers may start a request mid-tick (start = max(free
time, tick start)), so tick quantization does not waste bandwidth; the paper
samples throughput at 1 s, ≫ our default 1 ms tick.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from . import baselines
from .global_sync import sync_segments
from .job_table import JobTable, make_table
from .params import SchedulerParams, stack_params
from .policy import Policy, chain_elements
from .scheduler import Scheduler, TickView, get_scheduler
from .shard import (AXIS_SERVERS, AXIS_SWEEP, ShardSpec, resolve_shard,
                    state_specs)
from . import spans
from repro.compile_cache import cache_events
from repro.kernels.tick_step import tick_step, tick_step_grid

#: One entry is appended each time an engine scan is traced for XLA.
#: ``run``/``run_batch`` build a fresh jit per call, so every entry
#: corresponds to exactly one XLA compile; the sweep tests assert a whole
#: parameter grid lands in a single entry.  Entries are ``"<scheduler>"``
#: tags; clear the list before the region you want to count.
TRACE_LOG: list = []

# The workload-lowering vocabulary now lives in repro.scenario.lowering —
# the ONE canonical pipeline every construction path funnels through.  The
# engine re-exports the names (they are part of this module's public API
# and its tests' import surface); ``make_workload`` below is a consumer of
# ``lower()``, not an owner of its own dict-normalization.
from repro.scenario.lowering import (  # noqa: E402  (re-exports)
    ARRIVAL_CLOSED, ARRIVAL_INTERVAL, ARRIVAL_MODES, ARRIVAL_POISSON,
    I32_TICK_HORIZON, JOB_SPEC_KEYS, PHASE_SPEC_KEYS, lower_for_config,
    normalize_phases, validate_job_spec)
from repro.scenario.lowering import ticks_i32 as _ticks_i32  # noqa: E402,F401


def normalize_seed(seed):
    """One seed normalization for every PRNG path: uint32, two's complement
    for negatives, truncation for > 2**32.  ``run`` (Python int seed) and
    ``run_batch`` (traced seed lanes) both route through this, so any seed
    value produces bit-identical streams on both paths."""
    if isinstance(seed, (int, np.integer)):
        return np.uint32(int(seed) & 0xFFFFFFFF)
    return jnp.asarray(seed).astype(jnp.uint32)


def prng_key(seed) -> jax.Array:
    """``PRNGKey`` over the normalized seed (see :func:`normalize_seed`)."""
    return jax.random.PRNGKey(normalize_seed(seed))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-only configuration.

    Scheduler knobs live in the scheduler's own schema
    (:mod:`repro.core.params`): pass a frozen params instance via
    ``scheduler_params`` or leave it ``None`` for the schema defaults.  The
    flat per-scheduler knobs of earlier releases (``gift_*``, ``tbf_*``,
    ``adaptbf_*``, ``plan_*``) were removed after their deprecation cycle;
    passing one is now a ``TypeError`` at construction.
    """

    n_servers: int = 2
    max_jobs: int = 16
    n_workers: int = 8           # per server
    dt: float = 1e-3             # seconds per tick
    server_bw: float = 22e9      # bytes/s combined per server (paper §1: ~22 GB/s)
    wheel: int = 4096            # future-arrival time-wheel horizon (ticks)
    ring_cap: int = 512          # per (server, job) arrival-time ring
    bin_ticks: int = 100         # throughput bin (100 ms at dt=1 ms)
    # Any name in repro.core.scheduler.available_schedulers() — the registry,
    # not this comment, is the source of truth for what can run here.
    scheduler: str = "themis"
    policy: Optional[Policy] = None
    sync_ticks: int = 500        # λ in ticks; 0 disables sync (local-only view)
    sinkhorn_iters: int = 32
    # The scheduler's own knobs (repro.core.params schema matching
    # ``scheduler``); None -> schema defaults.
    scheduler_params: Optional[SchedulerParams] = None
    # Fabric-contention model for multi-server scaling: worker bandwidth is
    # derated by ``eff = n_servers ** (-fabric_exponent)``, a power-law loss
    # from cross-server fabric traffic (metadata, stripe coordination) as the
    # fleet grows.  0.0 (the default) models an ideal fabric — every server
    # delivers its full ``server_bw`` regardless of fleet size; the paper's
    # Fig. 7 scaling calibrates to ~S^-0.08 (82% efficiency at 8 servers,
    # 68% at 128).  See ``worker_bw``.
    fabric_exponent: float = 0.0
    # Worker-phase implementation: "ref" is the legacy per-worker lax.scan;
    # "pallas" routes the whole phase through the fused tick-step kernel
    # (repro.kernels.tick_step — bit-identical, interpret-mode off TPU);
    # "auto" picks pallas on TPU.  Schedulers without kernel support
    # (see Scheduler.kernel_tick) transparently fall back to "ref" — see
    # resolve_tick_impl.
    tick_impl: str = "auto"
    # Fleet sharding (repro.core.shard): split the [S, ...] server axis into
    # contiguous per-device slabs.  ``shard_servers=k`` is sugar for
    # ``mesh_shape=(1, k)``; ``mesh_shape=(m, k)`` additionally shards
    # run_batch's leading grid/seed axis over m sweep lanes.  The defaults
    # keep the classic single-device path (no shard_map in the trace), and a
    # sharded run is bit-identical to the unsharded one (tests/test_shard.py).
    shard_servers: int = 1
    mesh_shape: Optional[tuple] = None
    seed: int = 0

    def __post_init__(self):
        # Geometry must be validated here, at construction: a zero server
        # count otherwise surfaces deep inside a trace as an opaque
        # reshape/pow error 40 lines into make_tick.
        for name in ("n_servers", "max_jobs", "n_workers"):
            v = getattr(self, name)
            if not isinstance(v, (int, np.integer)) or v < 1:
                raise ValueError(
                    f"EngineConfig.{name} must be a positive int, got {v!r}")
        resolve_shard(self)   # mesh knobs: fail loudly before any tracing

    @property
    def worker_bw(self) -> float:
        """Per-worker bandwidth (bytes/s): the server's ``server_bw`` split
        evenly over its ``n_workers``, derated by the fabric-contention
        efficiency ``n_servers ** (-fabric_exponent)`` (1.0 at the default
        exponent of 0 — see ``fabric_exponent``)."""
        eff = float(self.n_servers) ** (-self.fabric_exponent)
        return self.server_bw / self.n_workers * eff


#: ``EngineConfig.tick_impl`` vocabulary.
TICK_IMPLS = ("auto", "ref", "pallas")


def resolve_tick_impl(cfg: "EngineConfig", sched: Scheduler) -> str:
    """Decide the worker-phase implementation for this (config, scheduler).

    ``ref`` always honors the request.  The fused path additionally needs the
    scheduler to be kernel-lowered: ``kernel_tick`` set AND ``charge`` still
    the base no-op (the kernel carries no aux state through the draws), else
    the request falls back to ``ref`` transparently — a non-lowered scheduler
    never errors, it just runs the scan.  ``auto`` resolves to ``pallas``
    only on TPU backends.  A server-sharded run (``mesh_shape``/
    ``shard_servers`` splitting the ``[S]`` axis) always runs the scan: the
    sharded tick keeps ring buffers device-local, which the fused kernel's
    monolithic ``[S, J, W]`` window does not.  A fallback warns nobody, but
    it is not hidden: :func:`run` / :func:`run_batch` report the resolved
    impl, and every ``RunResult`` carries it.
    """
    impl = cfg.tick_impl
    if impl not in TICK_IMPLS:
        raise ValueError(f"unknown tick_impl {impl!r}; one of {TICK_IMPLS}")
    shape = cfg.mesh_shape
    server_shards = int(shape[-1]) if shape else int(cfg.shard_servers)
    lowered = (sched.kernel_tick and type(sched).charge is Scheduler.charge
               and server_shards == 1)
    if impl == "ref" or not lowered:
        return "ref"
    if impl == "pallas":
        return "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "ref"


class Workload(NamedTuple):
    """Phased client population (static over a run).

    ``P`` is the scenario's phase count (max over jobs); jobs with fewer
    phases are padded with inactive rows (``phase_end <= phase_start``).
    A flat single-window spec is ``P = 1``.  ``req``/``think`` of the
    *current* phase (the most recently started one — held across idle gaps
    so a leftover backlog keeps its service profile) drive each tick.
    """

    phase_start: jnp.ndarray   # i32[J, P]  phase start tick
    phase_end: jnp.ndarray     # i32[J, P]  arrivals stop at/after this tick
    phase_req: jnp.ndarray     # f32[J, P]  request bytes while phase is current
    phase_think: jnp.ndarray   # i32[J, P]  closed-loop think ticks
    arrival_mode: jnp.ndarray  # i32[J, P]  ARRIVAL_CLOSED/_INTERVAL/_POISSON
    arrival_every: jnp.ndarray  # i32[J, P] inter-burst ticks (interval mode)
    arrival_rate: jnp.ndarray  # f32[J, P]  per-proc arrivals/tick (poisson)
    procs: jnp.ndarray         # i32[S, J]  client processes of job j on server s
    overhead_s: jnp.ndarray    # f32[J]  fixed per-request server cost

    # -- legacy single-phase views (the pre-scenario [J] fields) -------------
    @property
    def n_phases(self) -> int:
        return self.phase_start.shape[1]

    @property
    def start_tick(self) -> jnp.ndarray:
        """i32[J] first active phase start (horizon when never active)."""
        real = self.phase_end > self.phase_start
        return jnp.min(jnp.where(real, self.phase_start, I32_TICK_HORIZON),
                       axis=1).astype(jnp.int32)

    @property
    def end_tick(self) -> jnp.ndarray:
        """i32[J] last tick any phase issues arrivals."""
        return jnp.max(self.phase_end, axis=1)

    @property
    def req_bytes(self) -> jnp.ndarray:
        """f32[J] first-phase request size (the whole story when P = 1)."""
        return self.phase_req[:, 0]

    @property
    def think_ticks(self) -> jnp.ndarray:
        """i32[J] first-phase think time (the whole story when P = 1)."""
        return self.phase_think[:, 0]


class EngineState(NamedTuple):
    t: jnp.ndarray
    key: jax.Array
    qcount: jnp.ndarray       # i32[S, J]
    head: jnp.ndarray         # i32[S, J]
    arr_time: jnp.ndarray     # f32[S, J, CAP]
    wheel: jnp.ndarray        # i32[S, J, H]
    free_at: jnp.ndarray      # f32[S, W]
    known: jnp.ndarray        # bool[S, J]
    seg: jnp.ndarray          # f32[S, J]  λ-synced segments
    synced: jnp.ndarray       # bool[J]    included in last sync
    aux: baselines.AuxState
    bytes_bin: jnp.ndarray    # f32[J, NB]
    issued: jnp.ndarray       # i32[J]
    completed: jnp.ndarray    # i32[J]
    idle_worker_ticks: jnp.ndarray  # i32[] workers idle while demand existed
    dropped: jnp.ndarray      # i32[] arrivals rejected by full rings


def make_workload(
    cfg: EngineConfig,
    jobs: Sequence[dict],
) -> tuple[Workload, JobTable]:
    """Build a phased workload + job table from any scenario source.

    ``jobs`` is whatever :func:`repro.scenario.lowering.lower` accepts —
    a list of job spec dicts (see :data:`JOB_SPEC_KEYS`; unknown keys are
    a ``TypeError``), a ``Scenario``, or a combinator tree.  This is a
    thin consumer of the one canonical lowering pipeline: ``lower()``
    builds the validated ``[J, P]`` numpy arrays for ``cfg``'s geometry
    and this function wraps them into the jitted :class:`Workload` plus
    the job table.  A spec without ``phases`` lowers to ``P = 1`` and
    runs bit-identically to the pre-scenario engine.
    """
    low = lower_for_config(jobs, cfg)
    wl = Workload(
        phase_start=jnp.asarray(low.phase_start),
        phase_end=jnp.asarray(low.phase_end),
        phase_req=jnp.asarray(low.phase_req),
        phase_think=jnp.asarray(low.phase_think),
        arrival_mode=jnp.asarray(low.arrival_mode),
        arrival_every=jnp.asarray(low.arrival_every),
        arrival_rate=jnp.asarray(low.arrival_rate),
        procs=jnp.asarray(low.procs), overhead_s=jnp.asarray(low.overhead_s),
    )
    return wl, make_table(low.jobs, max_jobs=cfg.max_jobs)


def init_state(cfg: EngineConfig, n_bins: int) -> EngineState:
    s_, j_, w_ = cfg.n_servers, cfg.max_jobs, cfg.n_workers
    return EngineState(
        t=jnp.zeros((), jnp.int32),
        key=prng_key(cfg.seed),
        qcount=jnp.zeros((s_, j_), jnp.int32),
        head=jnp.zeros((s_, j_), jnp.int32),
        arr_time=jnp.zeros((s_, j_, cfg.ring_cap), jnp.float32),
        wheel=jnp.zeros((s_, j_, cfg.wheel), jnp.int32),
        free_at=jnp.zeros((s_, w_), jnp.float32),
        known=jnp.zeros((s_, j_), dtype=bool),
        seg=jnp.zeros((s_, j_), jnp.float32),
        synced=jnp.zeros((j_,), dtype=bool),
        aux=get_scheduler(cfg.scheduler).init_aux(s_, j_),
        bytes_bin=jnp.zeros((j_, n_bins), jnp.float32),
        issued=jnp.zeros((j_,), jnp.int32),
        completed=jnp.zeros((j_,), jnp.int32),
        idle_worker_ticks=jnp.zeros((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
    )


def _push_arrivals(state: EngineState, arrivals: jnp.ndarray, t_sec) -> EngineState:
    """Append `arrivals[s,j]` identically-timestamped requests to each ring.

    Arrivals beyond the ring's remaining capacity are rejected (not wrapped —
    wrapping would overwrite live entries and corrupt their arrival stamps)
    and tallied in ``EngineState.dropped`` so runs can assert zero loss.
    """
    cap = state.arr_time.shape[-1]
    space = jnp.maximum(cap - state.qcount, 0)
    accepted = jnp.minimum(arrivals, space)
    idx = jnp.arange(cap, dtype=jnp.int32)[None, None, :]
    tail = (state.head + state.qcount)[..., None]
    pos = (idx - tail) % cap
    mask = pos < accepted[..., None]
    arr_time = jnp.where(mask, jnp.float32(t_sec), state.arr_time)
    return state._replace(
        arr_time=arr_time,
        qcount=state.qcount + accepted,
        known=state.known | (accepted > 0),
        issued=state.issued + accepted.sum(axis=0).astype(jnp.int32),
        dropped=state.dropped + (arrivals - accepted).sum().astype(jnp.int32),
    )


def make_tick(cfg: EngineConfig, wl: Workload, table: JobTable, n_bins: int,
              shard: Optional[ShardSpec] = None):
    """Build the per-tick transition ``tick(p, state, _) -> (state, None)``.

    ``p`` is the scheduler's resolved params pytree; its numeric leaves may
    be tracers (jit arguments, vmap lanes), so everything downstream treats
    them as arrays.  ``cfg`` remains a static closure of engine geometry.

    With a server-sharding ``shard`` (``shard.n_servers > 1``) the returned
    tick expects *slab-local* state (``[S/k, ...]`` leaves, see
    :mod:`repro.core.shard`) and must run inside ``shard_map`` over the
    :data:`~repro.core.shard.AXIS_SERVERS` mesh axis: each tick all-gathers
    the small control plane (queue counters, heads, known/seg, free_at, aux
    — O(S·J) scalars), replays the *exact* single-device op sequence on the
    gathered arrays (same shapes, same PRNG draws, same scatter order — the
    bit-identity contract), and writes the heavy ring/wheel slabs
    (``O(S·J·CAP)``) strictly device-locally.
    """
    s_, j_, w_ = cfg.n_servers, cfg.max_jobs, cfg.n_workers
    cap, h_ = cfg.ring_cap, cfg.wheel
    worker_bw = cfg.worker_bw
    srv_idx = jnp.arange(s_, dtype=jnp.int32)
    sched = get_scheduler(cfg.scheduler)
    tick_impl = resolve_tick_impl(cfg, sched)
    # Hooks see the resolved impl: a scan-path draw (sharded runs, or an
    # explicit "ref") must never re-resolve "auto" to the kernel itself.
    cfg = dataclasses.replace(cfg, tick_impl=tick_impl)
    # Scenario geometry.  ``wl`` is concrete (a trace constant), so which
    # arrival machinery the tick needs is decided here in Python: a workload
    # with no open-loop phase traces the exact pre-scenario tick — same ops,
    # same PRNG stream — which is what keeps P=1 specs bit-identical.
    phase_real = wl.phase_end > wl.phase_start                     # [J, P]
    phase_idx = jnp.arange(wl.n_phases, dtype=jnp.int32)[None, :]
    mode_np = np.asarray(wl.arrival_mode)
    has_interval = bool((mode_np == ARRIVAL_INTERVAL).any())
    has_poisson = bool((mode_np == ARRIVAL_POISSON).any())
    # A closed phase that starts the tick its closed predecessor ends is a
    # *continuation*: the predecessor's population is still recycling, so
    # re-injecting procs would multiply the offered load (a 4-step ramp
    # would run 4x the clients by its last step).  Splitting one window
    # into contiguous closed phases must be a pure re-profiling.
    real_np = np.asarray(phase_real)
    contig = np.zeros_like(real_np)
    contig[:, 1:] = (real_np[:, 1:] & real_np[:, :-1]
                     & (np.asarray(wl.phase_start)[:, 1:]
                        == np.asarray(wl.phase_end)[:, :-1])
                     & (mode_np[:, 1:] == ARRIVAL_CLOSED)
                     & (mode_np[:, :-1] == ARRIVAL_CLOSED))
    fresh_start = jnp.asarray(~contig)                             # [J, P]

    def tick(p, state: EngineState, _):
        with jax.named_scope(spans.TICK_ARRIVALS):
            t = state.t
            t_sec = t.astype(jnp.float32) * cfg.dt
            started = (t >= wl.phase_start) & phase_real               # [J, P]
            phase_live = started & (t < wl.phase_end)
            live = phase_live.any(axis=1)
            # Current phase = most recently *started* real phase (held across
            # idle gaps so a leftover backlog keeps its request profile); 0
            # before any phase starts (no demand exists yet anyway).
            cur = jnp.maximum(jnp.max(jnp.where(started, phase_idx, -1),
                                      axis=1), 0)
            take_cur = lambda a: jnp.take_along_axis(a, cur[:, None], axis=1)[:, 0]
            req_now = take_cur(wl.phase_req)                           # f32[J]
            think_now = take_cur(wl.phase_think)                       # i32[J]
            recycle = live & (take_cur(wl.arrival_mode) == ARRIVAL_CLOSED)

            # -- 1. arrivals: time-wheel slot + phase starts + open-loop --------
            slot = jnp.mod(t, h_)
            inject = ((t == wl.phase_start) & phase_real & fresh_start
                      & (wl.arrival_mode == ARRIVAL_CLOSED)).any(axis=1)
            if has_interval:
                gap = jnp.mod(t - wl.phase_start,
                              jnp.maximum(wl.arrival_every, 1))
                inject = inject | (phase_live & (gap == 0)
                                   & (wl.arrival_mode == ARRIVAL_INTERVAL)
                                   ).any(axis=1)
            arrivals = state.wheel[:, :, slot] + jnp.where(
                inject[None, :], wl.procs, 0)
            key_carry = state.key
            if has_poisson:
                key_carry, kp = jax.random.split(state.key)
                lam = jnp.where(
                    phase_live & (wl.arrival_mode == ARRIVAL_POISSON),
                    wl.arrival_rate, 0.0).sum(axis=1)                  # f32[J]
                arrivals = arrivals + jax.random.poisson(
                    kp, lam[None, :] * wl.procs).astype(jnp.int32)
            state = state._replace(wheel=state.wheel.at[:, :, slot].set(0))
            state = _push_arrivals(state, arrivals, t_sec)

        with jax.named_scope(spans.TICK_SCHED):
            # -- 2. scheduler bookkeeping --------------------------------------
            ctrl = sched.ctrl_overhead_s(p)
            aux = sched.pre_tick(cfg, p, state.aux, state.qcount, t)
            shares = sched.tick_shares(cfg, table, TickView(
                qcount=state.qcount, known=state.known, seg=state.seg,
                synced=state.synced, live=live))

        with jax.named_scope(spans.TICK_WORKERS):
            # -- 3. workers: sequential pops within the tick --------------------
            key, sub = jax.random.split(key_carry)
            bytes_job = jnp.zeros((j_,), jnp.float32)
            pops_job = jnp.zeros((j_,), jnp.int32)
            idle_ticks = jnp.zeros((), jnp.int32)

            if tick_impl == "pallas":
                # Fused path: all W draws in one tick-step kernel invocation.
                # PRNG stream identity: the per-worker uniforms are precomputed
                # with the exact fold_in/uniform sequence the scan's select hook
                # consumes, so the run's key trajectory is unchanged.  Each
                # worker only ever reads/writes its own free_at column and
                # arr_time is read-only across the phase, so free/window can be
                # materialized up front; a worker pops at ring offset pops[s,j]
                # < W, which is why a [S, J, W] window covers every draw.  Only
                # fifo reads the window: themis draws from the shares alone.
                free = state.free_at < t_sec + cfg.dt                  # [S, W]
                u_all = jnp.stack(
                    [jax.random.uniform(jax.random.fold_in(sub, w), (s_,))
                     for w in range(w_)], axis=1)                      # [S, W]
                window = None
                if sched.kernel_select_mode == "fifo":
                    koff = jnp.arange(w_, dtype=jnp.int32)[None, None, :]
                    ring_idx = jnp.mod(state.head[..., None] + koff, cap)
                    window = jnp.take_along_axis(state.arr_time, ring_idx,
                                                 axis=-1)
                sel, valid, demand_any, qcount, pops_sj = tick_step(
                    shares, state.qcount, window, free, u_all,
                    mode=sched.kernel_select_mode, impl="pallas")
                head = jnp.mod(state.head + pops_sj, cap)
                arr_time = state.arr_time
                j_safe = jnp.maximum(sel, 0)                           # [S, W]
                rb = req_now[j_safe]
                service = rb / worker_bw + wl.overhead_s[j_safe] + ctrl
                start_t = jnp.maximum(state.free_at, t_sec)
                free_at = jnp.where(valid, start_t + service, state.free_at)
                off = jnp.clip(
                    jnp.ceil((free_at - t_sec) / cfg.dt).astype(jnp.int32)
                    + think_now[j_safe], 1, h_ - 1)
                slot2 = jnp.mod(t + off, h_)
                live_add = (valid & recycle[j_safe]).astype(jnp.int32)
                add_b = jnp.where(valid, rb, 0.0)
                wheel = state.wheel
                # Per-worker scatter order preserved (float adds must replay the
                # scan's accumulation order bit-for-bit).
                for w in range(w_):
                    wheel = wheel.at[srv_idx, j_safe[:, w], slot2[:, w]].add(
                        live_add[:, w])
                    bytes_job = bytes_job.at[j_safe[:, w]].add(add_b[:, w])
                    pops_job = pops_job.at[j_safe[:, w]].add(
                        valid[:, w].astype(jnp.int32))
                idle_ticks = (free & ~valid & demand_any).sum().astype(jnp.int32)
                # Lowered schedulers have the base no-op charge (checked by
                # resolve_tick_impl), so aux passes through from pre_tick.
                carry = (qcount, head, arr_time, wheel, free_at, aux, bytes_job,
                         pops_job, idle_ticks)
            else:
                def worker_body(carry, w):
                    (qcount, head, arr_time, wheel, free_at, aux, bytes_job,
                     pops_job, idle_ticks) = carry
                    kw = jax.random.fold_in(sub, w)
                    free = free_at[:, w] < t_sec + cfg.dt
                    demand = qcount > 0
                    head_time = jnp.where(
                        demand,
                        jnp.take_along_axis(arr_time, (head % cap)[..., None],
                                            axis=-1)[..., 0],
                        jnp.inf)
                    j_sel = sched.select(cfg, p, shares, head_time, demand, aux,
                                         req_now, kw)
                    valid = free & (j_sel >= 0)
                    j_safe = jnp.maximum(j_sel, 0)
                    onehot = (jax.nn.one_hot(j_safe, j_, dtype=jnp.int32)
                              * valid[:, None].astype(jnp.int32))
                    qcount = qcount - onehot
                    head = jnp.mod(head + onehot, cap)
                    rb = req_now[j_safe]
                    service = rb / worker_bw + wl.overhead_s[j_safe] + ctrl
                    start_t = jnp.maximum(free_at[:, w], t_sec)
                    new_free = jnp.where(valid, start_t + service, free_at[:, w])
                    free_at = free_at.at[:, w].set(new_free)
                    # closed-loop re-arrival after completion + think time
                    # (open-loop phases generate arrivals in step 1 instead
                    # of recycling pops)
                    job_live = recycle[j_safe]
                    off = (jnp.ceil((new_free - t_sec) / cfg.dt).astype(jnp.int32)
                           + think_now[j_safe])
                    off = jnp.clip(off, 1, h_ - 1)
                    slot2 = jnp.mod(t + off, h_)
                    wheel = wheel.at[srv_idx, j_safe, slot2].add(
                        (valid & job_live).astype(jnp.int32))
                    add_b = jnp.where(valid, rb, 0.0)
                    bytes_job = bytes_job.at[j_safe].add(add_b)
                    pops_job = pops_job.at[j_safe].add(valid.astype(jnp.int32))
                    aux = sched.charge(cfg, p, aux, srv_idx, j_safe, add_b)
                    idle_ticks = idle_ticks + (
                        free & ~valid & demand.any(axis=1)).sum().astype(jnp.int32)
                    return (qcount, head, arr_time, wheel, free_at, aux,
                            bytes_job, pops_job, idle_ticks), None

                carry = (state.qcount, state.head, state.arr_time, state.wheel,
                         state.free_at, aux, bytes_job, pops_job, idle_ticks)
                carry, _ = jax.lax.scan(worker_body, carry,
                                        jnp.arange(w_, dtype=jnp.int32))
        return _finish(state, carry, key, t, live)

    def _finish(state: EngineState, carry, key, t, live):
        """Steps shared by both worker-phase implementations: fold the phase
        results into the state (step 3 tail) and run the λ-sync (step 4)."""
        (qcount, head, arr_time, wheel, free_at, aux, bytes_job, pops_job,
         idle_ticks) = carry

        with jax.named_scope(spans.TICK_FINISH):
            b = jnp.minimum(t // cfg.bin_ticks, n_bins - 1)
            state = state._replace(
                t=t + 1, key=key, qcount=qcount, head=head, arr_time=arr_time,
                wheel=wheel, free_at=free_at, aux=aux,
                bytes_bin=state.bytes_bin.at[:, b].add(bytes_job),
                completed=state.completed + pops_job,
                idle_worker_ticks=state.idle_worker_ticks + idle_ticks,
            )

        # -- 4. λ-delayed global fairness sync ------------------------------
        if sched.uses_segments and cfg.sync_ticks > 0:
            def do_sync(st: EngineState) -> EngineState:
                support = st.known & live[None, :]
                seg = sync_segments(cfg.policy, table, support,
                                    n_iters=cfg.sinkhorn_iters)
                return st._replace(seg=seg, synced=support.any(axis=0))
            with jax.named_scope(spans.TICK_SYNC):
                state = jax.lax.cond(jnp.mod(state.t, cfg.sync_ticks) == 0,
                                     do_sync, lambda s: s, state)
        return state, None

    if shard is None or shard.n_servers == 1:
        return tick

    s_loc = s_ // shard.n_servers
    srv_loc = jnp.arange(s_loc, dtype=jnp.int32)

    def tick_sharded(p, state: EngineState, _):
        """Slab-local tick: state leaves in SLAB_FIELDS are ``[S/k, ...]``.

        Determinism: every decision below is computed on the all-gathered
        full-``[S]`` control plane with the single-device tick's op sequence
        — including the full-shape poisson/uniform draws and the per-worker
        float-scatter order — so each device independently reaches the same
        global decisions and only *applies* its own slab's rows.
        """
        def gat(x):
            return jax.lax.all_gather(x, AXIS_SERVERS, axis=0, tiled=True)

        def rows(x):
            return jax.lax.dynamic_slice_in_dim(x, row0, s_loc, axis=0)

        with jax.named_scope(spans.TICK_ARRIVALS):
            row0 = jax.lax.axis_index(AXIS_SERVERS).astype(jnp.int32) * s_loc
            t = state.t
            t_sec = t.astype(jnp.float32) * cfg.dt
            started = (t >= wl.phase_start) & phase_real
            phase_live = started & (t < wl.phase_end)
            live = phase_live.any(axis=1)
            cur = jnp.maximum(jnp.max(jnp.where(started, phase_idx, -1),
                                      axis=1), 0)
            take_cur = lambda a: jnp.take_along_axis(a, cur[:, None], axis=1)[:, 0]
            req_now = take_cur(wl.phase_req)
            think_now = take_cur(wl.phase_think)
            recycle = live & (take_cur(wl.arrival_mode) == ARRIVAL_CLOSED)

            # -- 1. arrivals: full-[S] accounting, slab-local ring writes -------
            slot = jnp.mod(t, h_)
            inject = ((t == wl.phase_start) & phase_real & fresh_start
                      & (wl.arrival_mode == ARRIVAL_CLOSED)).any(axis=1)
            if has_interval:
                gap = jnp.mod(t - wl.phase_start,
                              jnp.maximum(wl.arrival_every, 1))
                inject = inject | (phase_live & (gap == 0)
                                   & (wl.arrival_mode == ARRIVAL_INTERVAL)
                                   ).any(axis=1)
            arrivals = gat(state.wheel[:, :, slot]) + jnp.where(
                inject[None, :], wl.procs, 0)                          # [S, J]
            key_carry = state.key
            if has_poisson:
                key_carry, kp = jax.random.split(state.key)
                lam = jnp.where(
                    phase_live & (wl.arrival_mode == ARRIVAL_POISSON),
                    wl.arrival_rate, 0.0).sum(axis=1)
                arrivals = arrivals + jax.random.poisson(
                    kp, lam[None, :] * wl.procs).astype(jnp.int32)
            wheel = state.wheel.at[:, :, slot].set(0)                  # local
            qcount = gat(state.qcount)
            head = gat(state.head)
            known = gat(state.known)
            # _push_arrivals on the full control plane; the arr_time write (the
            # O(S·J·CAP) part) is masked down to this device's slab rows.
            space = jnp.maximum(cap - qcount, 0)
            accepted = jnp.minimum(arrivals, space)
            idx = jnp.arange(cap, dtype=jnp.int32)[None, None, :]
            tail = rows(head + qcount)[..., None]
            pos = (idx - tail) % cap
            mask = pos < rows(accepted)[..., None]
            arr_time = jnp.where(mask, jnp.float32(t_sec), state.arr_time)
            qcount = qcount + accepted
            known = known | (accepted > 0)
            issued = state.issued + accepted.sum(axis=0).astype(jnp.int32)
            dropped = state.dropped + (arrivals - accepted).sum().astype(jnp.int32)

        with jax.named_scope(spans.TICK_SCHED):
            # -- 2. scheduler bookkeeping on the gathered control plane ---------
            ctrl = sched.ctrl_overhead_s(p)
            seg = gat(state.seg)
            aux = jax.tree.map(gat, state.aux)
            aux = sched.pre_tick(cfg, p, aux, qcount, t)
            shares = sched.tick_shares(cfg, table, TickView(
                qcount=qcount, known=known, seg=seg,
                synced=state.synced, live=live))

        with jax.named_scope(spans.TICK_WORKERS):
            # -- 3. workers -----------------------------------------------------
            key, sub = jax.random.split(key_carry)
            bytes_job = jnp.zeros((j_,), jnp.float32)
            pops_job = jnp.zeros((j_,), jnp.int32)
            idle_ticks = jnp.zeros((), jnp.int32)
            free_at = gat(state.free_at)
            # The only ring data the worker phase can touch: worker w pops at
            # ring offset pops[s, j] <= w < W, so a W-wide window starting at
            # head covers every head_time read this tick.  Gathering the window
            # ([S, J, W]) instead of the ring ([S, J, CAP]) is what keeps the
            # heavy slab local.
            koff = jnp.arange(w_, dtype=jnp.int32)[None, None, :]
            ring_idx = jnp.mod(rows(head)[..., None] + koff, cap)
            window = gat(jnp.take_along_axis(arr_time, ring_idx, axis=-1))

            def worker_body(carry, w):
                (qcount, head, pops, wheel, free_at, aux, bytes_job, pops_job,
                 idle_ticks) = carry
                kw = jax.random.fold_in(sub, w)
                free = free_at[:, w] < t_sec + cfg.dt
                demand = qcount > 0
                head_time = jnp.where(
                    demand,
                    jnp.take_along_axis(
                        window, jnp.minimum(pops, w_ - 1)[..., None],
                        axis=-1)[..., 0],
                    jnp.inf)
                j_sel = sched.select(cfg, p, shares, head_time, demand, aux,
                                     req_now, kw)
                valid = free & (j_sel >= 0)
                j_safe = jnp.maximum(j_sel, 0)
                onehot = (jax.nn.one_hot(j_safe, j_, dtype=jnp.int32)
                          * valid[:, None].astype(jnp.int32))
                qcount = qcount - onehot
                head = jnp.mod(head + onehot, cap)
                pops = pops + onehot
                rb = req_now[j_safe]
                service = rb / worker_bw + wl.overhead_s[j_safe] + ctrl
                start_t = jnp.maximum(free_at[:, w], t_sec)
                new_free = jnp.where(valid, start_t + service, free_at[:, w])
                free_at = free_at.at[:, w].set(new_free)
                job_live = recycle[j_safe]
                off = (jnp.ceil((new_free - t_sec) / cfg.dt).astype(jnp.int32)
                       + think_now[j_safe])
                off = jnp.clip(off, 1, h_ - 1)
                slot2 = jnp.mod(t + off, h_)
                add = (valid & job_live).astype(jnp.int32)
                wheel = wheel.at[srv_loc, rows(j_safe), rows(slot2)].add(rows(add))
                add_b = jnp.where(valid, rb, 0.0)
                bytes_job = bytes_job.at[j_safe].add(add_b)
                pops_job = pops_job.at[j_safe].add(valid.astype(jnp.int32))
                aux = sched.charge(cfg, p, aux, srv_idx, j_safe, add_b)
                idle_ticks = idle_ticks + (
                    free & ~valid & demand.any(axis=1)).sum().astype(jnp.int32)
                return (qcount, head, pops, wheel, free_at, aux, bytes_job,
                        pops_job, idle_ticks), None

            carry = (qcount, head, jnp.zeros((s_, j_), jnp.int32), wheel,
                     free_at, aux, bytes_job, pops_job, idle_ticks)
            carry, _ = jax.lax.scan(worker_body, carry,
                                    jnp.arange(w_, dtype=jnp.int32))
            (qcount, head, _pops, wheel, free_at, aux, bytes_job, pops_job,
             idle_ticks) = carry

        # -- 4. finish: replicated fold + λ-sync, slab slice-back -----------
        new_t = t + 1
        synced = state.synced
        if sched.uses_segments and cfg.sync_ticks > 0:
            def do_sync(args):
                sg, sn = args
                support = known & live[None, :]
                return (sync_segments(cfg.policy, table, support,
                                      n_iters=cfg.sinkhorn_iters),
                        support.any(axis=0))
            with jax.named_scope(spans.TICK_SYNC):
                seg, synced = jax.lax.cond(
                    jnp.mod(new_t, cfg.sync_ticks) == 0, do_sync,
                    lambda a: a, (seg, synced))
        with jax.named_scope(spans.TICK_FINISH):
            b = jnp.minimum(t // cfg.bin_ticks, n_bins - 1)
            state = state._replace(
                t=new_t, key=key, qcount=rows(qcount), head=rows(head),
                arr_time=arr_time, wheel=wheel, free_at=rows(free_at),
                known=rows(known), seg=rows(seg), synced=synced,
                aux=jax.tree.map(rows, aux),
                bytes_bin=state.bytes_bin.at[:, b].add(bytes_job),
                issued=issued, completed=state.completed + pops_job,
                idle_worker_ticks=state.idle_worker_ticks + idle_ticks,
                dropped=dropped)
        return state, None

    return tick_sharded


def _call_marks() -> tuple:
    """What the per-call counters count from: jit traces so far and the
    compile cache's ``(requests, hits)``."""
    return (len(TRACE_LOG),) + cache_events()


def _kernel_grid_steps(cfg: EngineConfig, sched: Scheduler, tick_impl: str,
                       lanes: int) -> int:
    """Grid steps of one fused kernel invocation, 0 on the scan path: the
    vmap lanes one device runs fold into the kernel's rows, which
    :func:`~repro.kernels.tick_step.kernel.tick_step_grid` blocks."""
    if tick_impl != "pallas":
        return 0
    shard = resolve_shard(cfg)
    per_device = max(1, lanes // (shard.n_sweep if shard else 1))
    return tick_step_grid(per_device * cfg.n_servers, cfg.max_jobs,
                          cfg.n_workers, sched.kernel_select_mode)[1]


def _call_counters(marks: tuple, cfg: EngineConfig, sched: Scheduler,
                   ticks: int, lanes: int, tick_impl: str) -> dict:
    """One engine call's counters (see ``RunResult.counters``)."""
    traces, requests, hits = marks
    now_requests, now_hits = cache_events()
    # The share table's policy chain, built per server every tick.
    chain = sched.uses_segments
    return {
        "lanes": lanes,
        # One fused invocation per tick serves every lane (vmap folds them).
        "kernel_invocations": ticks if tick_impl == "pallas" else 0,
        "kernel_grid_steps": _kernel_grid_steps(cfg, sched, tick_impl, lanes),
        "policy_levels": cfg.policy.depth if chain else 0,
        "chain_elements": (cfg.n_servers * chain_elements(cfg.policy,
                                                          cfg.max_jobs)
                           if chain else 0),
        "jit_traces": len(TRACE_LOG) - traces,
        "compile_cache_requests": now_requests - requests,
        "compile_cache_hits": now_hits - hits,
    }


def _execute(fn, args):
    """Call the jitted program and wait for the device, under the dispatch
    and device-wait spans.  The wait adds none: the first host copy of the
    result would block there anyway."""
    with jax.profiler.TraceAnnotation(spans.ENGINE_DISPATCH):
        state = fn(*args)
    with jax.profiler.TraceAnnotation(spans.ENGINE_DEVICE_WAIT):
        jax.block_until_ready(state)
    return state


def _run_program(cfg: EngineConfig, wl: Workload, table: JobTable,
                 sim_seconds: float):
    """The jitted whole-run program and its arguments: ``(fn, args, ticks)``
    with ``fn(*args)`` the final :class:`EngineState`."""
    ticks = int(round(sim_seconds / cfg.dt))
    n_bins = max(1, (ticks + cfg.bin_ticks - 1) // cfg.bin_ticks)
    shard = resolve_shard(cfg)
    tick = make_tick(cfg, wl, table, n_bins, shard=shard)
    state = init_state(cfg, n_bins)
    params = get_scheduler(cfg.scheduler).params(cfg)

    def _body(p, state):
        TRACE_LOG.append(cfg.scheduler)
        state, _ = jax.lax.scan(lambda s, x: tick(p, s, x), state, None,
                                length=ticks)
        return state

    if shard is None:
        _run = jax.jit(_body)
    else:
        specs = state_specs(state, shard)
        _run = jax.jit(jax.shard_map(
            _body, mesh=shard.mesh(), in_specs=(P(), specs), out_specs=specs,
            check_vma=False))
    return _run, (params, state), ticks


def lower_run(cfg: EngineConfig, wl: Workload, table: JobTable,
              sim_seconds: float) -> jax.stages.Lowered:
    """The program :func:`run` executes, lowered but not run — e.g.
    ``.compile().as_text()`` shows whether the fused kernel is in it."""
    fn, args, _ = _run_program(cfg, wl, table, sim_seconds)
    return fn.lower(*args)


def run(cfg: EngineConfig, wl: Workload, table: JobTable, sim_seconds: float):
    """Run the simulation; returns the final state and per-bin throughput.

    Args:
      cfg: engine geometry + scheduler selection (static for the trace).
      wl/table: from :func:`make_workload` — the phased client population
        and the policy-attribute job table.
      sim_seconds: simulated horizon; ``ticks = sim_seconds / cfg.dt``.

    Returns a dict: ``state`` (final :class:`EngineState`), ``gbps[J, NB]``
    (job j's throughput in GB/s per ``bin_s``-second bin), the
    ``issued``/``completed``/``dropped``/``idle_worker_ticks`` counters, and
    ``tick_impl`` — the worker-phase implementation that actually ran
    (:func:`resolve_tick_impl`).

    With ``cfg.mesh_shape``/``shard_servers`` set, the scan runs under
    ``shard_map`` with each device owning a server slab (see
    :mod:`repro.core.shard`); results are bit-identical to the single-device
    path.  A sweep axis in ``mesh_shape`` is idle here (one run has no grid
    axis) — lanes replicate over it.
    """
    marks = _call_marks()
    with jax.profiler.TraceAnnotation(spans.ENGINE_PREPARE):
        fn, args, ticks = _run_program(cfg, wl, table, sim_seconds)
        sched = get_scheduler(cfg.scheduler)
        tick_impl = resolve_tick_impl(cfg, sched)
    state = _execute(fn, args)
    bin_s = cfg.bin_ticks * cfg.dt
    with jax.profiler.TraceAnnotation(spans.ENGINE_FETCH):
        out = {
            "state": state,
            "gbps": np.asarray(state.bytes_bin) / bin_s / 1e9,
            "bin_s": bin_s,
            "issued": np.asarray(state.issued),
            "completed": np.asarray(state.completed),
            "dropped": int(state.dropped),
            "idle_worker_ticks": int(state.idle_worker_ticks),
            "ticks": ticks,
            "tick_impl": tick_impl,
        }
    out["counters"] = _call_counters(marks, cfg, sched, ticks, 1, tick_impl)
    return out


def run_batch(cfg: EngineConfig, wl: Workload, table: JobTable,
              sim_seconds: float, *, seeds: Sequence[int],
              params_points: Optional[Sequence[SchedulerParams]] = None):
    """Run the simulation over PRNG seeds — and optionally a params grid —
    in ONE compile.

    Every seed (and grid point) shares the workload, table, and engine
    geometry; only the PRNG stream and the scheduler's numeric knobs differ,
    so the whole batch is ``vmap`` over the initial key (and the params
    leaves) and each lane is bit-identical to a sequential :func:`run` with
    ``cfg.seed = s`` (and ``cfg.scheduler_params = p``).

    Without ``params_points`` every returned array carries a leading
    ``K = len(seeds)`` axis.  With ``params_points`` (a sequence of concrete
    params instances for ``cfg.scheduler`` — same schema, same ``mu_ticks``)
    arrays carry ``[P, K, ...]``: P grid points × K seeds, the paper-style
    mean + coefficient-of-variation sweep from a single compile.

    Sharding (:mod:`repro.core.shard`): a ``servers`` mesh axis slabs the
    ``[S]`` dimension exactly as in :func:`run`; a ``sweep`` mesh axis
    additionally splits the *leading grid axis* — ``params_points`` lanes
    when given (each device sweeps its own slice of the grid), else the
    seeds axis — which must divide evenly.  Lanes are independent
    simulations, so the sweep axis needs no collectives, and every lane
    stays bit-identical to its sequential :func:`run`.
    """
    seeds = [int(normalize_seed(s)) for s in seeds]
    sched = get_scheduler(cfg.scheduler)
    points = None if params_points is None else list(params_points)
    for p in points or ():
        if type(p) is not sched.params_cls:
            raise TypeError(
                f"params_points entries must be {sched.params_cls.__name__} "
                f"for scheduler {cfg.scheduler!r}, got {type(p).__name__}")
    marks = _call_marks()
    with jax.profiler.TraceAnnotation(spans.ENGINE_PREPARE):
        fn, args, ticks = _batch_program(cfg, wl, table, sim_seconds, seeds,
                                         points)
        tick_impl = resolve_tick_impl(cfg, sched)
    state = _execute(fn, args)
    bin_s = cfg.bin_ticks * cfg.dt
    with jax.profiler.TraceAnnotation(spans.ENGINE_FETCH):
        out = {
            "state": state,
            "seeds": np.asarray(seeds, dtype=np.uint32),
            "gbps": np.asarray(state.bytes_bin) / bin_s / 1e9,   # [(P,) K, J, NB]
            "bin_s": bin_s,
            "issued": np.asarray(state.issued),                  # [(P,) K, J]
            "completed": np.asarray(state.completed),            # [(P,) K, J]
            "dropped": np.asarray(state.dropped),                # [(P,) K]
            "idle_worker_ticks": np.asarray(state.idle_worker_ticks),  # [(P,) K]
            "ticks": ticks,
            "tick_impl": tick_impl,
        }
    lanes = len(seeds) * (1 if points is None else len(points))
    out["counters"] = _call_counters(marks, cfg, sched, ticks, lanes,
                                     tick_impl)
    return out


def _batch_program(cfg: EngineConfig, wl: Workload, table: JobTable,
                   sim_seconds: float, seeds: Sequence[int],
                   points: Optional[list]):
    """:func:`run_batch`'s jitted program and its arguments, like
    :func:`_run_program`: ``(fn, args, ticks)``."""
    ticks = int(round(sim_seconds / cfg.dt))
    n_bins = max(1, (ticks + cfg.bin_ticks - 1) // cfg.bin_ticks)
    shard = resolve_shard(cfg)
    tick = make_tick(cfg, wl, table, n_bins, shard=shard)
    base = init_state(cfg, n_bins)
    sched = get_scheduler(cfg.scheduler)
    params = sched.params(cfg) if points is None else stack_params(points)
    seed_arr = jnp.asarray(seeds, dtype=jnp.uint32)
    # The explicit index supplies the mapped-axis size even for schemas with
    # no numeric leaves (themis/fifo), where ``params`` alone carries no
    # axis; under a sweep-sharded mesh it is also what splits the grid.
    point_idx = jnp.arange(len(points) if points is not None else 1)

    def _body(p, seed_arr, point_idx, base):
        TRACE_LOG.append(cfg.scheduler)

        def one_seed(pp, seed):
            st = base._replace(key=prng_key(seed))
            st, _ = jax.lax.scan(lambda s, x: tick(pp, s, x), st, None,
                                 length=ticks)
            return st

        def per_seed(pp):
            return jax.vmap(lambda s: one_seed(pp, s))(seed_arr)

        if points is None:
            return per_seed(p)
        return jax.vmap(lambda pp, _i: per_seed(pp),
                        in_axes=(0, 0))(p, point_idx)

    if shard is None:
        _run_all = jax.jit(_body)
    else:
        shard_grid = shard.n_sweep > 1
        if shard_grid:
            n_lanes = len(points) if points is not None else len(seeds)
            what = "params_points" if points is not None else "seeds"
            if n_lanes % shard.n_sweep:
                raise ValueError(
                    f"len({what})={n_lanes} is not divisible by the mesh's "
                    f"sweep axis ({shard.n_sweep}); each device sweeps an "
                    "equal slice of the grid")
        sweep = AXIS_SWEEP if shard_grid else None
        lead = (sweep, None) if points is not None else (sweep,)
        grid_spec = P(sweep)
        in_specs = ((grid_spec if points is not None else P()),
                    (grid_spec if points is None else P()),
                    (grid_spec if points is not None else P()),
                    state_specs(base, shard))
        _run_all = jax.jit(jax.shard_map(
            _body, mesh=shard.mesh(), in_specs=in_specs,
            out_specs=state_specs(base, shard, lead=lead),
            check_vma=False))

    return _run_all, (params, seed_arr, point_idx, base), ticks
