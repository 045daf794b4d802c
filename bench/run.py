"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload fig12.themis --seed 7 --seconds 40 --trace 0

One run:

1. finds the cell's configuration and traffic mix by name
   (:mod:`bench.spec`);
2. fails without a TPU, or with fewer chips than the cell asks for;
3. turns on the persistent compile cache inside the checkout and makes one
   warm call of exactly the program the window runs (set-up ends here);
4. runs whole experiment calls (``Experiment.run_batch`` or
   ``Experiment.run``) back to back until ``--seconds`` have passed, call
   ``i`` on the PRNG seeds drawn from ``--seed`` and ``i``;
5. with ``--trace 1`` the window's first calls (``TRACED_SECONDS``) run
   under the profiler; once the window has closed, the program those calls
   ran is lowered and compiled again for its op names, and the per-layer
   metrics are read from the trace (:mod:`bench.trace`), the program's
   spans and scopes in it (:mod:`bench.spans`) and the calls' counters
   (``bench/metrics``);
6. compares a sample of the window's calls, drawn from ``--seed``, with the
   configuration's plain reference (:func:`bench.spec.load_reference`,
   :mod:`bench.check`);
7. prints counters on earlier lines, the compared numbers beside their
   limits as the last lines of standard error, and one JSON object as the
   last line of standard output.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

# libtpu would otherwise write its logs to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import check, spans, spec, trace  # noqa: E402
from bench.peaks import device_peaks  # noqa: E402


#: Seconds of the window that a ``--trace 1`` run profiles (whole calls).
TRACED_SECONDS = 10.0


class CacheCounter:
    """Counts compilations that asked the persistent cache and missed."""

    def __init__(self):
        self.requests = 0
        self.hits = 0

    def __call__(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def misses(self) -> int:
        return self.requests - self.hits


#: ``EngineConfig`` fields a configuration may not set: the traffic mix
#: names the scheduler and its parameters, ``--seed`` gives the PRNG seeds,
#: and a cell measures the worker-phase path ``auto`` gives users.
NOT_FROM_CONFIG = ("scheduler", "scheduler_params", "seed", "tick_impl")


def engine_options(config: dict) -> dict:
    """Every key of the configuration that names an ``EngineConfig`` field
    (lists as tuples); the harness's own keys and the descriptive ones are
    not engine options."""
    from repro.core.engine import EngineConfig

    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    owned = sorted(set(NOT_FROM_CONFIG) & set(config))
    if owned:
        raise ValueError(f"a configuration may not set {owned}")
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in config.items() if k in fields}


def build_experiment(cell: spec.Cell):
    """The cell's ``Experiment``: the configuration's engine options and job
    population, served by the traffic mix's scheduler."""
    from repro.api import Experiment
    from repro.core.scheduler import get_scheduler

    tr = cell.traffic
    params = get_scheduler(tr["scheduler"]).params_cls(**tr.get("params", {}))
    exp = Experiment(scheduler=tr["scheduler"], params=params,
                     **engine_options(cell.config))
    return exp.add_jobs(spec.make_jobs(cell.config))


def experiment_call(exp, config: dict, seeds: tuple):
    """One whole experiment call, as a user makes it; returns when the
    device is done (the entry points copy their counters to the host)."""
    if config["entry"] == "run_batch":
        return exp.run_batch(config["sim_seconds"], seeds=seeds)
    if config["entry"] != "run" or len(seeds) != 1:
        raise ValueError(f"entry {config['entry']!r} with {len(seeds)} lanes")
    exp.seed = seeds[0]
    return exp.run(config["sim_seconds"])


def lower_call(exp, config: dict, seeds: tuple):
    """The program one experiment call on ``seeds`` runs, lowered and not
    run: the same entry, geometry and lane count."""
    from repro.core import engine

    if config["entry"] == "run_batch":
        fn, args, _ = engine._batch_program(
            *exp.build(), config["sim_seconds"],
            [int(engine.normalize_seed(s)) for s in seeds], None)
        return fn.lower(*args)
    exp.seed = seeds[0]
    return engine.lower_run(*exp.build(), config["sim_seconds"])


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             *, compile_cache: bool = True, log=print) -> dict:
    """Set up, run the window, read the trace, check; returns the result
    object (without printing it)."""
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    if compile_cache:
        from repro.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        log(f"compile_cache={cache_dir}")
    from jax import monitoring

    from bench import reference
    from repro.core import engine

    lanes = int(cfg["lanes"])
    devices = jax.devices()
    device = devices[0]
    exp = build_experiment(cell)
    warm = experiment_call(exp, cfg, spec.call_seeds(seed, 0, lanes))
    tick_impl = warm.tick_impl
    del warm
    setup_s = time.perf_counter() - T_PROCESS

    counter = CacheCounter()
    monitoring.register_event_listener(counter)
    n_trace0 = len(engine.TRACE_LOG)
    calls = []

    def calls_until(t0: float, until: float) -> float:
        """Whole calls back to back until ``until`` seconds after ``t0``;
        returns the end of the last."""
        while True:
            seeds = spec.call_seeds(seed, len(calls) + 1, lanes)
            with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
                res = experiment_call(exp, cfg, seeds)
            te = time.perf_counter()
            calls.append((seeds, res))
            if te - t0 >= until:
                return te

    t0 = time.perf_counter()
    if traced:
        # The profiler covers the window's first calls only: a trace of
        # every op of every tick grows with its length.
        tracedir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans from the runtime only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tracedir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            te = calls_until(t0, min(seconds, TRACED_SECONDS))
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        stop_s = time.perf_counter() - t_stop
        n_traced = len(calls)
    if not traced or te - t0 < seconds:
        te = calls_until(t0, seconds)
    wall = te - t0
    misses = counter.misses
    monitoring.unregister_event_listener(counter)
    trace_entries = (len(engine.TRACE_LOG) - n_trace0) / len(calls)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:cell.chips]]
    peak = max((p for p in peaks if p is not None), default=None)

    n_calls = len(calls)
    dropped = sum(int(np.asarray(r.dropped).sum()) for _, r in calls)
    idle = sum(int(np.asarray(r.idle_worker_ticks).sum()) for _, r in calls)
    log(f"cell={cell.name} config={cell.config_name} traffic="
        f"{cell.traffic_name} tick_impl={tick_impl} calls={n_calls} "
        f"lanes={lanes} sim_seconds={cfg['sim_seconds']} wall_s={wall!r}")
    log(f"trace_log_entries_per_call={trace_entries!r} "
        f"compile_cache_misses_in_window={misses} "
        f"compile_requests_in_window={counter.requests} "
        f"dropped={dropped} idle_worker_ticks={idle} "
        f"peak_bytes_in_use={peak} setup_s={setup_s!r}")

    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind, "count": len(devices),
                         "memory_peak_bytes": peak}}
    ticks = reference.n_ticks(cfg)
    if traced:
        # A TPU trace names each op by its instruction only; the scopes
        # come from the compiled text of the program the traced calls ran,
        # compiled again here (the persistent cache has it), after the
        # window and the memory peak.
        t_read = [time.perf_counter()]
        names = spans.op_names(
            lower_call(exp, cfg, calls[0][0]).compile().as_text())
        t_read.append(time.perf_counter())
        events = spans.load(trace.trace_file(tracedir), names)
        shutil.rmtree(tracedir, ignore_errors=True)
        t_read.append(time.perf_counter())
        red, sred = trace.reduce_events(events), spans.reduce_spans(events)
        t_read.append(time.perf_counter())
        del events
        counters = [r.counters() for _, r in calls[:n_traced]]
        ctx = dict(reduction=red, spans=sred, counters=counters, cell=cell,
                   ticks=ticks, lanes=lanes, device_kind=device.device_kind)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
        log(f"trace: calls={n_traced} window_s={red.window_s!r} "
            f"busy_s={red.busy_s!r} "
            f"device_events={red.n_device_events} "
            f"kernel_events={red.n_kernel_events} "
            f"named_kernel_events={sred and sred.n_kernel_events} "
            f"kernel_invocations="
            f"{sum(c['kernel_invocations'] for c in counters)} "
            f"op_names={len(names)}")
        log(f"trace_seconds: stop={stop_s!r} lower_compile="
            f"{t_read[1] - t_read[0]!r} load={t_read[2] - t_read[1]!r} "
            f"reduce={t_read[3] - t_read[2]!r}")
    else:
        metrics = {"sim_s_per_s": {
            "value": n_calls * lanes * cfg["sim_seconds"] / wall,
            "unit": "sim-s/s"}, "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}

    # The check: one of the window's calls, drawn from the seed, against the
    # reference, once the window has closed and the rest is freed.  Its
    # reference run takes about as long as the call itself.
    t_check = time.perf_counter()
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2**64, 2**32]))
    picked = [int(rng.integers(n_calls))]
    sample = [(calls[i][0], check.program_state(calls[i][1].state, lanes))
              for i in picked]
    del calls
    gc.collect()
    per_field = {}
    bad_lanes = 0
    ref_mod = spec.load_reference(cell)
    sim = ref_mod.make_simulator(
        cfg, cell.traffic, spec.make_jobs(cfg), float_dtype=jnp.float32,
        tick_end=reference.tick_end_rounding(cfg["dt"]))
    for seeds, prog in sample:
        ref = ref_mod.run_lanes(sim, cell.traffic, seeds)
        mm = check.mismatches(prog, ref)
        for k, v in mm.items():
            per_field[k] = per_field.get(k, 0) + v
        bad_lanes += check.lanes_differing(prog, ref, lanes)
    correct, numbers = check.verdict(per_field)
    log(f"check: seconds={time.perf_counter() - t_check!r} "
        f"calls={[int(i) for i in picked]} of {n_calls} "
        f"lanes={len(sample) * lanes} mismatched_by_field="
        f"{ {k: v for k, v in per_field.items() if v} }")
    result.update(correct=correct, attempted=n_calls * lanes,
                  failed=bad_lanes, metrics=metrics,
                  check={k: {"value": v, "limit": lim}
                         for k, (v, lim) in numbers.items()})
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.resolve_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    device_peaks(devices[0].device_kind)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["check"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown", "check")
    print(json.dumps({k: result[k] for k in order if k in result}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
