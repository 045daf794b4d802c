"""Names of the spans and scopes the program writes into a profiler trace.

A ``jax.profiler`` trace of an experiment call shows two vocabularies:

* **host spans** (``jax.profiler.TraceAnnotation``), one of each per
  engine call, in this order: :data:`EXPERIMENT_BUILD` (the facade builds
  the workload and job table), :data:`ENGINE_PREPARE` (the tick function,
  the initial state, the params and a fresh ``jax.jit``),
  :data:`ENGINE_DISPATCH` (the call of the jitted program: trace, lower,
  cache load or compile, enqueue; JAX's own runtime spans nest inside it),
  :data:`ENGINE_DEVICE_WAIT` (blocking until the device has the final
  state) and :data:`ENGINE_FETCH` (copying the counters to the host);
* **device scopes** (``jax.named_scope``), which put every operation of a
  simulated tick under one of :data:`TICK_PHASES` in its ``op_name``.

With the profiler off an annotation costs a few microseconds per call and
a scope nothing at run time: neither changes what the program computes.
"""
from __future__ import annotations

EXPERIMENT_BUILD = "experiment.build"
ENGINE_PREPARE = "engine.prepare"
ENGINE_DISPATCH = "engine.dispatch"
ENGINE_DEVICE_WAIT = "engine.device_wait"
ENGINE_FETCH = "engine.fetch"
#: The host spans of one experiment call, in the order they open.
HOST_SPANS = (EXPERIMENT_BUILD, ENGINE_PREPARE, ENGINE_DISPATCH,
              ENGINE_DEVICE_WAIT, ENGINE_FETCH)

#: Arrivals: phase selection, the time-wheel slot, injections, ring pushes.
TICK_ARRIVALS = "tick/arrivals"
#: Scheduler bookkeeping: ``pre_tick`` and the per-tick share table.
TICK_SCHED = "tick/sched"
#: The worker phase: the fused kernel branch or the W-step worker scan.
TICK_WORKERS = "tick/workers"
#: The fold of the worker phase into the state, and throughput binning.
TICK_FINISH = "tick/finish"
#: The lambda-delayed global fairness sync (its ``cond``).
TICK_SYNC = "tick/sync"
TICK_PHASES = (TICK_ARRIVALS, TICK_SCHED, TICK_WORKERS, TICK_FINISH,
               TICK_SYNC)
