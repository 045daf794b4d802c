"""Plain reference simulator: the burst-buffer semantics the engine states,
written out tick by tick and worker by worker, independent of its code.

It takes a configuration and a job population (as ``bench.spec`` builds
them) and the PRNG seeds of one experiment call, and returns the final
state the engine is documented to reach, under the engine's field names:
per (server, job) queue counts, the arrival ring and its head, the
future-arrival wheel, per-worker free times, the λ-synced segments,
scheduler accounts, per-bin bytes and the request counters.

What it shares with the engine is only what the simulated system is:

* the PRNG stream of a seed (``PRNGKey(seed)``, split once per tick, one
  ``fold_in`` per worker) — the seed *is* the random draw sequence;
* the float expressions that decide a request's fate (service time, free
  time, re-arrival offset, token refills).  A simulation is chaotic in
  the last bit: one draw that lands on the other side of a segment
  boundary, or one worker free a tick early, changes every later tick.  So
  the reference adds a server's segment table in the engine's documented
  log-step order (lane ``i`` adds lane ``i - k`` for ``k = 1, 2, 4,
  ...``), evaluates the policy chain as the paper's matrix product
  (Eq. 1), and rounds the end of a tick as the backend rounds ``t·dt +
  dt`` (:func:`tick_end_rounding`).

Supported: closed-loop single-window jobs, the ``themis`` and ``tbf``
schedulers, policies whose levels all weigh ``fair`` (every such named
chain, and any ``entity:fair,...`` chain; :func:`policy_levels`).
Anything else raises, so a cell the reference cannot judge never reads as
correct.  ``float_dtype`` runs every float of the state in another
precision: the control, which has to come out as not correct.

**The reference contract.**  Each configuration is judged by one plain
reference: the file its ``"reference"`` key names (a path under
``bench/``, e.g. ``bench/references/<config>.py``), or this module where
it names none.  A deployment whose mechanism this module does not model
(open-loop arrivals, other weights or schedulers) brings a file of its
own, and the harness loads it by path (``bench.spec.load_reference``).
Such a file

* shares no code with the program under test (it imports nothing of
  ``repro``); it may import this module's helpers (:func:`lower_jobs`,
  :func:`chain_shares`, :func:`token_draw`, ...);
* defines ``make_simulator(config, traffic, jobs, float_dtype=...,
  tick_end=...)``, where ``tick_end`` is :func:`tick_end_rounding` of the
  backend, and ``run_lanes(sim, traffic, seeds)``, which returns the final
  state of one experiment call under the engine's field names (scheduler
  accounts as ``aux.<field>``), as numpy arrays with a leading lane axis,
  one lane per seed;
* reads not correct when built with ``float_dtype=jnp.bfloat16``: that is
  its control, and ``bench/control.py`` runs it.

:func:`tick_end_rounding` and :func:`n_ticks` stay here: they are facts
about the backend and the configuration that every reference shares.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

I32_MAX = int(np.iinfo(np.int32).max)

#: The paper's named policies as ``entity:weight`` chains, coarse to fine.
NAMED_POLICIES = {
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
ENTITIES = ("group", "user", "job")

SCHEDULERS = ("themis", "tbf")
AUX_FIELDS = ("budget", "coupons", "served", "bucket", "spare", "borrowed",
              "ema", "plan")


def _ticks(seconds: float, dt: float) -> int:
    return int(min(round(float(seconds) / dt), I32_MAX))


def lower_jobs(config: dict, jobs: list[dict]) -> dict:
    """Per-job arrays of the population: start/end tick, request bytes,
    think ticks, client processes per server, the policy attributes."""
    s_, j_, dt = config["n_servers"], config["max_jobs"], config["dt"]
    if len(jobs) > j_:
        raise ValueError(f"{len(jobs)} jobs > {j_} slots")
    out = dict(start=np.zeros(j_, np.int32), end=np.zeros(j_, np.int32),
               req=np.ones(j_, np.float32), think=np.zeros(j_, np.int32),
               procs=np.zeros((s_, j_), np.int32),
               overhead=np.zeros(j_, np.float32),
               user=np.zeros(j_, np.int32), group=np.zeros(j_, np.int32),
               active=np.zeros(j_, bool))
    for j, spec in enumerate(jobs):
        if spec.get("phases") or spec.get("arrival", "closed") != "closed":
            raise NotImplementedError(
                f"job {j}: the reference models closed-loop single-window "
                "jobs only")
        out["start"][j] = _ticks(spec.get("start_s", 0.0), dt)
        out["end"][j] = _ticks(spec.get("end_s", 1e9), dt)
        out["req"][j] = float(spec.get("req_mb", 10.0)) * 1e6
        out["think"][j] = _ticks(spec.get("think_s", 0.0), dt)
        servers = list(spec.get("servers", range(s_)))
        n = int(spec.get("procs", spec.get("size", 1) * 56))
        for i, sv in enumerate(servers):
            out["procs"][sv, j] += n // len(servers) + (i < n % len(servers))
        out["overhead"][j] = float(spec.get("overhead_us", 0.0)) * 1e-6
        out["user"][j] = int(spec.get("user", 0))
        out["group"][j] = int(spec.get("group", 0))
        out["active"][j] = True
    return out


def policy_levels(policy: str) -> tuple[str, ...]:
    """The sharing entities of a policy chain, coarse to fine, ending in
    ``job``: a named policy or an ``entity[:weight],...`` chain (weight
    ``fair`` where none is given).  Only ``fair`` weights are modelled; any
    other raises."""
    chain = NAMED_POLICIES.get(policy, policy)
    levels = []
    for part in chain.split(","):
        entity, _, weight = part.strip().partition(":")
        if entity not in ENTITIES:
            raise ValueError(f"policy {policy!r}: unknown entity {entity!r}")
        if (weight or "fair") != "fair":
            raise NotImplementedError(
                f"policy {policy!r}: the reference models fair weights "
                f"only, not {weight!r}")
        levels.append(entity)
    if levels[-1] != "job":
        levels.append("job")
    ranks = [ENTITIES.index(e) for e in levels]
    if any(b <= a for a, b in zip(ranks, ranks[1:])):
        raise ValueError(f"policy {policy!r}: levels must run coarse to fine")
    return tuple(levels)


def chain_shares(levels, mask, user, group, fdt):
    """Eq. 1 for fair-weighted levels: ``mask`` bool[J] marks the jobs that
    take part; each level splits its parent's share equally over its live
    children.  Returns f[J], the product taken at full precision in
    ``fdt``."""
    j_ = mask.shape[-1]
    vec = jnp.ones((1, 1), fdt)
    prev_ids = jnp.zeros((j_,), jnp.int32)
    prev_dim = 1
    for entity in levels:
        raw = {"job": jnp.arange(j_, dtype=jnp.int32), "user": user,
               "group": group}[entity]
        if entity == "job":
            cid, dim = raw, j_
        else:
            cid, dim = prev_ids * j_ + raw, prev_dim * j_
        live = jax.ops.segment_max(mask.astype(jnp.int32), cid,
                                   num_segments=dim) > 0
        parent = jax.ops.segment_max(jnp.where(mask, prev_ids, -1), cid,
                                     num_segments=dim)
        t = ((parent[None, :] == jnp.arange(prev_dim)[:, None])
             & live[None, :]).astype(fdt)
        rows = t.sum(axis=1, keepdims=True)
        t = jnp.where(rows > 0, t / jnp.maximum(rows, 1e-30), 0.0)
        vec = jnp.matmul(vec, t, precision=jax.lax.Precision.HIGHEST)
        prev_ids, prev_dim = cid, dim
    return vec[0]


def sinkhorn(support, col_targets, n_iters: int, fdt):
    """Per-server segments whose columns follow the global shares over the
    servers each job touches, every row summing to one (paper §3.1)."""
    s_ = support.shape[0]
    row_t = jnp.full((s_,), 1.0 / s_, dtype=fdt)
    col_live = (support.sum(axis=0) > 0) & (col_targets > 0)
    col_t = jnp.where(col_live, col_targets, 0.0)
    col_t = col_t / jnp.maximum(col_t.sum(), 1e-30)
    a = support * col_t[None, :]

    def step(_, a):
        csum = a.sum(axis=0)
        a = a * jnp.where(csum > 0, col_t / jnp.maximum(csum, 1e-30),
                          0.0)[None, :]
        rsum = a.sum(axis=1, keepdims=True)
        return a * jnp.where(rsum > 0, row_t[:, None]
                             / jnp.maximum(rsum, 1e-30), 0.0)

    a = jax.lax.fori_loop(0, n_iters, step, a)
    rsum = a.sum(axis=1, keepdims=True)
    return jnp.where(rsum > 0, a / jnp.maximum(rsum, 1e-30), 0.0)


def log_step_prefix(x):
    """Inclusive prefix sum over the last axis, adding lane ``i - k`` into
    lane ``i`` for ``k = 1, 2, 4, ...``."""
    n = x.shape[-1]
    k = 1
    while k < n:
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[..., :k]), x[..., :n - k]], axis=-1)
        x = x + shifted
        k *= 2
    return x


def token_draw(shares, demand, u):
    """Statistical-token draw per server: the demanded job whose segment
    holds ``u`` (scaled to the table's total); uniform over demanded jobs
    when none has share mass; -1 when nothing is demanded."""
    j_ = shares.shape[-1]
    dm = demand.astype(shares.dtype)
    masked = shares * dm
    has_mass = (masked > 0).any(axis=-1, keepdims=True)
    seg = log_step_prefix(jnp.where(has_mass, masked, dm))
    total = seg[:, j_ - 1]
    idx = (seg <= (u * total)[:, None]).sum(axis=-1).astype(jnp.int32)
    idx = jnp.where(total > 0, jnp.minimum(idx, j_ - 1), -1)
    lane = jnp.arange(j_, dtype=jnp.int32)
    first = jnp.min(jnp.where(demand, lane, j_), axis=-1)
    picked_has = jnp.take_along_axis(demand, jnp.maximum(idx, 0)[:, None],
                                     axis=-1)[:, 0]
    return jnp.where((idx >= 0) & ~picked_has, first, idx)


def weighted_pick(w, key):
    """Weighted pick per server over cumulative weights; -1 for rows with
    no weight."""
    j_ = w.shape[-1]
    total = w.sum(axis=-1)
    u = (jax.random.uniform(key, (w.shape[0],)).astype(w.dtype)
         * jnp.maximum(total, 1e-30))
    cdf = jnp.cumsum(w, axis=-1)
    idx = jnp.clip((cdf <= u[:, None]).sum(axis=-1), 0, j_ - 1)
    has = jnp.take_along_axis(w, idx[:, None], axis=-1)[:, 0] > 0
    first = jnp.argmax(w > 0, axis=-1)
    idx = jnp.where(has, idx, first)
    return jnp.where(total > 0, idx, -1).astype(jnp.int32)


def n_ticks(config: dict) -> int:
    return int(round(config["sim_seconds"] / config["dt"]))


def n_bins(config: dict) -> int:
    return max(1, -(-n_ticks(config) // config["bin_ticks"]))


def initial_state(config: dict, seed, fdt) -> dict:
    s_, j_, w_ = config["n_servers"], config["max_jobs"], config["n_workers"]
    zf = jnp.zeros((s_, j_), fdt)
    return dict(
        t=jnp.zeros((), jnp.int32),
        key=jax.random.PRNGKey(seed),
        qcount=jnp.zeros((s_, j_), jnp.int32),
        head=jnp.zeros((s_, j_), jnp.int32),
        arr_time=jnp.zeros((s_, j_, config["ring_cap"]), fdt),
        wheel=jnp.zeros((s_, j_, config["wheel"]), jnp.int32),
        free_at=jnp.zeros((s_, w_), fdt),
        known=jnp.zeros((s_, j_), bool),
        seg=zf,
        synced=jnp.zeros((j_,), bool),
        **{f"aux.{k}": (jnp.zeros((s_,), fdt) if k == "spare" else zf)
           for k in AUX_FIELDS},
        bytes_bin=jnp.zeros((j_, n_bins(config)), fdt),
        issued=jnp.zeros((j_,), jnp.int32),
        completed=jnp.zeros((j_,), jnp.int32),
        idle_worker_ticks=jnp.zeros((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
    )


def tick_end_rounding(dt: float) -> str:
    """How this backend evaluates the end of tick ``t``, ``t·dt + dt``, in
    float32: ``"single"`` when it contracts the two into one fused
    multiply-add (one rounding, the same as ``(t + 1)·dt``), ``"two"`` when
    it rounds the product and then the sum.  A worker whose free time lies
    on a tick boundary is free or busy by this last bit, so the reference
    follows the backend it checks."""
    t = np.arange(1 << 16, dtype=np.int32)
    got = np.asarray(jax.jit(lambda t: t.astype(jnp.float32) * dt + dt)(t))
    tf, d = t.astype(np.float32), np.float32(dt)
    if np.array_equal(got, (tf + 1) * d):
        return "single"
    if np.array_equal(got, tf * d + d):
        return "two"
    raise RuntimeError("the backend rounds t*dt + dt neither once nor twice")


def make_simulator(config: dict, traffic: dict, jobs: list[dict],
                   float_dtype=jnp.float32, tick_end: str = "single"):
    """``sim(seeds, params) -> final state`` with a leading lane axis, one
    lane per seed; ``params`` are the scheduler's numeric knobs (f32
    scalars, runtime arguments as in the engine).  ``tick_end`` is
    :func:`tick_end_rounding` of the backend."""
    sched = traffic["scheduler"]
    if sched not in SCHEDULERS:
        raise NotImplementedError(f"the reference models {SCHEDULERS}, "
                                  f"not {sched!r}")
    fdt = float_dtype
    arr = lower_jobs(config, jobs)
    s_, j_, w_ = config["n_servers"], config["max_jobs"], config["n_workers"]
    cap, h_, dt = config["ring_cap"], config["wheel"], config["dt"]
    worker_bw = (config["server_bw"] / w_
                 * float(s_) ** (-config.get("fabric_exponent", 0.0)))
    nb = n_bins(config)
    start, end = jnp.asarray(arr["start"]), jnp.asarray(arr["end"])
    req = jnp.asarray(arr["req"]).astype(fdt)
    think, procs = jnp.asarray(arr["think"]), jnp.asarray(arr["procs"])
    overhead = jnp.asarray(arr["overhead"]).astype(fdt)
    user, group = jnp.asarray(arr["user"]), jnp.asarray(arr["group"])
    active = jnp.asarray(arr["active"])
    srv = jnp.arange(s_, dtype=jnp.int32)
    real = end > start
    if sched == "themis":
        levels = policy_levels(config["policy"])
        sync_ticks, iters = config["sync_ticks"], config["sinkhorn_iters"]
        shares_of = functools.partial(chain_shares, levels, user=user,
                                      group=group, fdt=fdt)
    else:
        mu_ticks = int(traffic["params"]["mu_ticks"])
        mu_s = mu_ticks * dt

    def tick(p, st, _):
        st = dict(st)
        t = st["t"]
        t_sec = t.astype(fdt) * dt
        t_next = ((t + 1).astype(fdt) * dt if tick_end == "single"
                  else jax.lax.optimization_barrier(t_sec) + dt)
        live = (t >= start) & real & (t < end)
        slot = jnp.mod(t, h_)

        # 1. arrivals: the wheel's slot plus clients starting this tick
        inject = (t == start) & real
        arrivals = st["wheel"][:, :, slot] + jnp.where(inject[None, :],
                                                       procs, 0)
        wheel = st["wheel"].at[:, :, slot].set(0)
        q, head = st["qcount"], st["head"]
        accepted = jnp.minimum(arrivals, jnp.maximum(cap - q, 0))
        ring_pos = jnp.mod(jnp.arange(cap)[None, None, :]
                           - (head + q)[..., None], cap)
        arr_time = jnp.where(ring_pos < accepted[..., None],
                             t_sec.astype(fdt), st["arr_time"])
        q = q + accepted
        known = st["known"] | (accepted > 0)
        issued = st["issued"] + accepted.sum(axis=0)
        dropped = st["dropped"] + (arrivals - accepted).sum()

        # 2. scheduler bookkeeping and this tick's share table
        bucket, spare, served = (st["aux.bucket"], st["aux.spare"],
                                 st["aux.served"])
        ctrl = 0.0
        if sched == "tbf":
            rate = jnp.where(p["rate"] > 0, p["rate"],
                             config["server_bw"] / j_).astype(fdt)
            ctrl = p["ctrl_overhead_s"].astype(fdt)
            bucket = jnp.minimum(bucket + rate * dt,
                                 rate * p["burst_s"].astype(fdt))
            boundary = jnp.mod(t, mu_ticks) == 0
            guaranteed = jnp.minimum(served, rate * mu_s).sum(axis=1)
            new_spare = p["headroom"].astype(fdt) * jnp.maximum(
                config["server_bw"] * mu_s - guaranteed, 0.0)
            spare = jnp.where(boundary, new_spare, spare)
            served = jnp.where(boundary, 0.0, served).astype(fdt)
        else:
            demand = q > 0
            local = jax.vmap(lambda d: shares_of(active & d))(
                known & live[None, :] & demand)
            base = jnp.where(st["synced"][None, :], st["seg"], local)
            has_mass = (base * demand > 0).any(axis=-1, keepdims=True)
            shares = jnp.where(has_mass, base, local)

        # 3. workers pop one request each, in worker order
        key, sub = jax.random.split(st["key"])
        free_at = st["free_at"]
        bytes_job = jnp.zeros((j_,), fdt)
        pops_job = jnp.zeros((j_,), jnp.int32)
        idle = jnp.zeros((), jnp.int32)
        for w in range(w_):
            kw = jax.random.fold_in(sub, w)
            free = free_at[:, w] < t_next
            demand = q > 0
            if sched == "themis":
                u = jax.random.uniform(kw, (s_,)).astype(fdt)
                pick = token_draw(shares, demand, u)
            else:
                covered = demand & (bucket >= req[None, :])
                adm = weighted_pick(
                    jnp.where(covered, jnp.maximum(bucket, 1.0), 0.0), kw)
                spare_open = spare > req.max()
                lend = weighted_pick(
                    jnp.where(demand & spare_open[:, None], 1.0, 0.0)
                    .astype(fdt), jax.random.fold_in(kw, 1))
                pick = jnp.where(covered.any(axis=-1), adm, lend)
            valid = free & (pick >= 0)
            j_sel = jnp.maximum(pick, 0)
            popped = valid.astype(jnp.int32)
            q = q.at[srv, j_sel].add(-popped)
            head = head.at[srv, j_sel].set(
                jnp.mod(head[srv, j_sel] + popped, cap))
            rb = req[j_sel]
            service = rb / worker_bw + overhead[j_sel] + ctrl
            busy_until = jnp.maximum(free_at[:, w], t_sec) + service
            new_free = jnp.where(valid, busy_until, free_at[:, w])
            free_at = free_at.at[:, w].set(new_free)
            off = (jnp.ceil((new_free - t_sec) / dt).astype(jnp.int32)
                   + think[j_sel])
            back = jnp.mod(t + jnp.clip(off, 1, h_ - 1), h_)
            wheel = wheel.at[srv, j_sel, back].add(
                (valid & live[j_sel]).astype(jnp.int32))
            add_b = jnp.where(valid, rb, 0.0).astype(fdt)
            bytes_job = bytes_job.at[j_sel].add(add_b)
            pops_job = pops_job.at[j_sel].add(popped)
            if sched == "tbf":
                from_bucket = jnp.minimum(
                    add_b, jnp.maximum(bucket[srv, j_sel], 0.0))
                bucket = bucket.at[srv, j_sel].add(-from_bucket)
                spare = spare.at[srv].add(-(add_b - from_bucket))
                served = served.at[srv, j_sel].add(add_b)
            idle = idle + (free & ~valid & demand.any(axis=1)).sum()

        # 4. bin the bytes, then the λ-sync of the global segments
        b = jnp.minimum(t // config["bin_ticks"], nb - 1)
        st.update(
            t=t + 1, key=key, qcount=q, head=head, arr_time=arr_time,
            wheel=wheel, free_at=free_at, known=known,
            bytes_bin=st["bytes_bin"].at[:, b].add(bytes_job),
            issued=issued, completed=st["completed"] + pops_job,
            idle_worker_ticks=st["idle_worker_ticks"] + idle,
            dropped=dropped)
        st["aux.bucket"], st["aux.spare"], st["aux.served"] = (
            bucket, spare, served)
        if sched == "themis" and sync_ticks > 0:
            def do_sync(args):
                support = known & live[None, :]
                g = shares_of(active & support.any(axis=0))
                return (sinkhorn(support.astype(fdt), g, iters, fdt),
                        support.any(axis=0))
            st["seg"], st["synced"] = jax.lax.cond(
                jnp.mod(t + 1, sync_ticks) == 0, do_sync, lambda a: a,
                (st["seg"], st["synced"]))
        return st, None

    ticks = n_ticks(config)

    def one_lane(seed, p):
        st = initial_state(config, seed, fdt)
        st, _ = jax.lax.scan(functools.partial(tick, p), st, None,
                             length=ticks)
        return st

    @jax.jit
    def sim(seeds, params):
        return jax.vmap(one_lane, in_axes=(0, None))(seeds, params)

    return sim


def reference_params(traffic: dict) -> dict:
    """The scheduler's numeric knobs as f32 runtime scalars."""
    return {k: jnp.float32(v) for k, v in traffic.get("params", {}).items()
            if k != "mu_ticks"}


def run_lanes(sim, traffic: dict, seeds) -> dict:
    """Final states of ``sim`` (from :func:`make_simulator`) for the PRNG
    ``seeds`` (one lane each), as numpy arrays keyed by the engine's field
    names."""
    out = sim(jnp.asarray(np.asarray(seeds, np.uint32)),
              reference_params(traffic))
    return {k: np.asarray(v) for k, v in out.items()}


def simulate(config: dict, traffic: dict, jobs: list[dict], seeds,
             float_dtype=jnp.float32) -> dict:
    """:func:`run_lanes` of a simulator built for this one call."""
    sim = make_simulator(config, traffic, jobs, float_dtype,
                         tick_end_rounding(config["dt"]))
    return run_lanes(sim, traffic, seeds)
