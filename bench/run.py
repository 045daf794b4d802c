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
   under the profiler and the per-layer metrics are read from their trace
   (:mod:`bench.trace`, ``bench/metrics``);
6. compares a sample of the window's calls, drawn from ``--seed``, with the
   plain reference (:mod:`bench.reference`, :mod:`bench.check`);
7. prints counters on earlier lines, the compared numbers beside their
   limits as the last lines of standard error, and one JSON object as the
   last line of standard output.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
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

from bench import check, spec, trace  # noqa: E402
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


def build_experiment(cell: spec.Cell):
    """The cell's ``Experiment``: the configuration's geometry and job
    population, served by the traffic mix's scheduler."""
    from repro.api import Experiment
    from repro.core.scheduler import get_scheduler

    c, tr = cell.config, cell.traffic
    params = get_scheduler(tr["scheduler"]).params_cls(**tr.get("params", {}))
    exp = Experiment(
        policy=c["policy"], scheduler=tr["scheduler"], params=params,
        n_servers=c["n_servers"], n_workers=c["n_workers"],
        server_bw=c["server_bw"], max_jobs=c["max_jobs"], dt=c["dt"],
        wheel=c["wheel"], ring_cap=c["ring_cap"], bin_ticks=c["bin_ticks"],
        sync_ticks=c["sync_ticks"], sinkhorn_iters=c["sinkhorn_iters"],
        fabric_exponent=c["fabric_exponent"])
    return exp.add_jobs(spec.make_jobs(c))


def experiment_call(exp, config: dict, seeds: tuple):
    """One whole experiment call, as a user makes it; returns when the
    device is done (the entry points copy their counters to the host)."""
    if config["entry"] == "run_batch":
        return exp.run_batch(config["sim_seconds"], seeds=seeds)
    if config["entry"] != "run" or len(seeds) != 1:
        raise ValueError(f"entry {config['entry']!r} with {len(seeds)} lanes")
    exp.seed = seeds[0]
    return exp.run(config["sim_seconds"])


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             *, compile_cache: bool = True, log=print) -> dict:
    """Set up, run the window, read the trace, check; returns the result
    object (without printing it)."""
    import jax

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
        jax.profiler.stop_trace()
        n_traced = len(calls)
    if not traced or te - t0 < seconds:
        te = calls_until(t0, seconds)
    wall = te - t0
    misses = counter.misses
    monitoring.unregister_event_listener(counter)
    trace_entries = (len(engine.TRACE_LOG) - n_trace0) / len(calls)
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")

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
        red = trace.reduce_dir(tracedir)
        shutil.rmtree(tracedir, ignore_errors=True)
        ctx = dict(reduction=red, cell=cell, ticks=ticks, lanes=lanes,
                   device_kind=device.device_kind)
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
            f"kernel_events={red.n_kernel_events}")
    else:
        metrics = {"sim_s_per_s": {
            "value": n_calls * lanes * cfg["sim_seconds"] / wall,
            "unit": "sim-s/s"}, "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}

    # The check: one of the window's calls, drawn from the seed, against the
    # reference, once the window has closed and the rest is freed.  Its
    # reference run takes about as long as the call itself.
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2**64, 2**32]))
    picked = [int(rng.integers(n_calls))]
    sample = [(calls[i][0], check.program_state(calls[i][1].state, lanes))
              for i in picked]
    del calls
    gc.collect()
    per_field = {}
    bad_lanes = 0
    sim = reference.make_simulator(
        cfg, cell.traffic, spec.make_jobs(cfg),
        tick_end=reference.tick_end_rounding(cfg["dt"]))
    for seeds, prog in sample:
        ref = reference.run_lanes(sim, cell.traffic, seeds)
        mm = check.mismatches(prog, ref)
        for k, v in mm.items():
            per_field[k] = per_field.get(k, 0) + v
        bad_lanes += check.lanes_differing(prog, ref, lanes)
    correct, numbers = check.verdict(per_field)
    log(f"check: calls={[int(i) for i in picked]} of {n_calls} "
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
