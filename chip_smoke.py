"""Chip smoke test: drive the ThemisIO engine and service plane on a TPU.

    python chip_smoke.py             # phases a-c on one chip
    python chip_smoke.py --chips 4   # phase d only, on four chips

Everything runs in this one process, through the ``repro.api.Experiment``
entry points; it starts no child process.  Phases, each printing one line
per run (phase, resolved ``tick_impl``, wall seconds with compilation
included, issued/completed/dropped requests and aggregate GB/s):

  a. Fleet geometry (``benchmarks/bench_fleet.py``: S=128 servers, J=1024
     jobs, W=4 workers, 0.1 s simulated = 500 ticks).  themis must resolve
     to the fused Pallas kernel and its compiled program must contain a
     ``tpu_custom_call``; the fused run must equal the ``tick_impl="ref"``
     scan bit for bit (completed, issued, dropped, bytes_bin, qcount, PRNG
     key), and so must fifo's.
  b. Paper geometry (``benchmarks/bench_comparison.make_jobs``): every
     registered scheduler runs ``run_batch`` over 8 seeds for 1 s; each
     lane must conserve requests (issued == completed + queued).
  c. Service plane: ``Experiment.serve()`` with themis on 8 servers; four
     clients write striped random chunks, then read every chunk back, and
     the bytes must be identical.
  d. (``--chips 4`` only) phase a's themis and fifo specs sharded over 4
     chips (``shard_servers=4``) must equal the single-chip ``ref`` run
     bit for bit.

Any failed check raises, and the script exits non-zero.  The last line of
standard output is one JSON object naming the device.  Without a TPU the
script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: Fleet geometry of ``benchmarks/bench_fleet.py`` (full defaults).
FLEET = dict(policy="user-fair", n_servers=128, max_jobs=1024, n_workers=4,
             dt=2e-4, wheel=128, ring_cap=16, bin_ticks=500)
FLEET_SECONDS = 0.1
#: EngineState fields whose final values must agree between two runs.
COMPARED = ("completed", "issued", "dropped", "bytes_bin", "qcount", "key")


def _line(phase: str, res, wall_s: float) -> None:
    print(f"phase={phase} tick_impl={res.tick_impl} "
          f"wall_s={wall_s:.3f} (compile incl) "
          f"issued={int(np.asarray(res.issued).sum())} "
          f"completed={int(np.asarray(res.completed).sum())} "
          f"dropped={int(np.asarray(res.dropped).sum())} "
          f"agg_gbps={float(np.asarray(res.gbps).sum(axis=-2).mean()):.3f}",
          flush=True)


def _timed_run(exp, seconds: float):
    t0 = time.perf_counter()
    res = exp.run(seconds)
    np.asarray(res.state.bytes_bin)          # block until the device is done
    return res, time.perf_counter() - t0


def _assert_equal_states(a, b, what: str, fields=COMPARED) -> None:
    import jax

    for name in fields:
        xs, ys = (jax.tree.leaves(getattr(st, name)) for st in (a, b))
        for x, y in zip(xs, ys, strict=True):
            x, y = np.asarray(x), np.asarray(y)
            if x.shape != y.shape or not np.array_equal(x, y):
                diff = (int((x != y).sum()) if x.shape == y.shape
                        else f"{x.shape} vs {y.shape}")
                raise AssertionError(f"{what}: {name} differs ({diff})")


def assert_kernel_compiled(exp, seconds: float) -> None:
    """The program ``exp.run`` compiles holds the Mosaic kernel."""
    from repro.core.engine import lower_run

    text = lower_run(*exp.build(), seconds).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{exp.scheduler} program holds no "
                             "tpu_custom_call")


def phase_fleet(fleet: dict, jobs, seconds: float) -> None:
    from repro.api import Experiment

    for sched in ("themis", "fifo"):
        fused = Experiment(scheduler=sched, **fleet).add_jobs(jobs)
        res, wall = _timed_run(fused, seconds)
        _line(f"a.{sched}.auto", res, wall)
        if res.tick_impl != "pallas":
            raise AssertionError(f"{sched} resolved to {res.tick_impl!r}, "
                                 "not the fused kernel")
        if res.completed.sum() == 0:
            raise AssertionError(f"{sched}: no request completed")
        if sched == "themis":
            assert_kernel_compiled(fused, seconds)
        ref = Experiment(scheduler=sched, tick_impl="ref",
                         **fleet).add_jobs(jobs)
        res_ref, wall = _timed_run(ref, seconds)
        _line(f"a.{sched}.ref", res_ref, wall)
        _assert_equal_states(res.state, res_ref.state,
                             f"{sched} fused vs ref")
        print(f"phase=a.{sched} fused == ref: "
              f"{', '.join(COMPARED)} bit-identical", flush=True)


def phase_paper(seconds: float, seeds) -> None:
    from benchmarks.bench_comparison import make_jobs
    from repro.api import Experiment
    from repro.core import available_schedulers

    jobs = make_jobs(seconds)
    bin_ticks = max(1, int(round(min(1.0, seconds / 10) / 1e-3)))
    for sched in available_schedulers():
        exp = Experiment(policy="job-fair", scheduler=sched,
                         bin_ticks=bin_ticks).add_jobs(jobs)
        t0 = time.perf_counter()
        res = exp.run_batch(seconds, seeds=seeds)
        queued = np.asarray(res.state.qcount).sum(axis=(1, 2))
        _line(f"b.{sched}", res, time.perf_counter() - t0)
        issued = res.issued.sum(axis=1)
        completed = res.completed.sum(axis=1)
        if not np.array_equal(issued, completed + queued):
            raise AssertionError(f"{sched}: issued {issued} != completed "
                                 f"{completed} + queued {queued}")
        if not (completed > 0).all() or not np.isfinite(res.gbps).all():
            raise AssertionError(f"{sched}: idle lane or non-finite GB/s")


def _striped_writes(n_servers: int, n_clients: int, n_chunks: int,
                    chunk: int, seed: int, tick_impl: str):
    """Stand up the service plane and drain one round of striped writes:
    returns ``(service, files, data, completed writes in drain order)``."""
    from repro.api import Experiment

    exp = Experiment(policy="user-fair", scheduler="themis",
                     n_servers=n_servers, seed=seed, tick_impl=tick_impl)
    for c in range(n_clients):
        exp.add_job(user=c % 2, size=n_servers)
    svc = exp.serve(autodrain=False, stripes=n_servers)
    files = [c.open(f"/ckpt_{i}", "w") for i, c in enumerate(svc.clients)]
    svc.drain()                          # creates land before striped writes
    rng = np.random.default_rng(seed)
    data = [[rng.bytes(chunk) for _ in range(n_chunks)] for _ in files]
    for f, blobs in zip(files, data):
        for blob in blobs:
            f.write(blob)
    return svc, files, data, svc.drain()


def phase_service(n_servers: int, n_clients: int, n_chunks: int,
                  chunk: int, seed: int) -> None:
    from repro.kernels.token_select.ops import resolve_impl

    geometry = (n_servers, n_clients, n_chunks, chunk, seed)
    t0 = time.perf_counter()
    svc, files, data, writes = _striped_writes(*geometry, tick_impl="auto")
    for c in svc.clients:
        c.autodrain = True               # each read drains and returns bytes
    back = []
    for f in files:
        f.seek(0)
        back.append([f.read(chunk) for _ in range(n_chunks)])
    wall = time.perf_counter() - t0
    n_req = n_clients * n_chunks
    n_done = len(writes) + sum(b is not None for bs in back for b in bs)
    servers = {s.sid for s in svc.cluster.servers
               if any(op == "write" for _, _, op in s.processed)}
    vt = svc.cluster.clock
    print(f"phase=c.service tick_impl={resolve_impl(svc.cluster.cfg.tick_impl)}"
          f" wall_s={wall:.3f} (compile incl) issued={2 * n_req} "
          f"completed={n_done} dropped=0 "
          f"agg_gbps={2 * n_req * chunk / vt / 1e9:.3f} (virtual clock)"
          f" servers_written={len(servers)}", flush=True)
    if len(writes) != n_req or any(r.op != "write" for r in writes):
        raise AssertionError(f"{len(writes)} of {n_req} writes completed")
    if back != data:
        raise AssertionError("bytes read back differ from bytes written")
    if len(servers) != n_servers:
        raise AssertionError(f"writes reached {len(servers)} of {n_servers} "
                             "servers; striping did not spread them")
    ref = _striped_writes(*geometry, tick_impl="ref")[3]
    order = [(r.job.job_id, r.seqno, r.done_at) for r in writes]
    if order != [(r.job.job_id, r.seqno, r.done_at) for r in ref]:
        raise AssertionError("service drain order differs from the ref draw")
    print(f"phase=c.service drain order == ref: {len(order)} writes",
          flush=True)


def phase_sharded(fleet: dict, jobs, seconds: float, n_chips: int) -> None:
    from repro.api import Experiment

    for sched in ("themis", "fifo"):
        shard = Experiment(scheduler=sched, shard_servers=n_chips,
                           **fleet).add_jobs(jobs)
        res, wall = _timed_run(shard, seconds)
        _line(f"d.{sched}.x{n_chips}", res, wall)
        ref = Experiment(scheduler=sched, tick_impl="ref",
                         **fleet).add_jobs(jobs)
        res_ref, wall = _timed_run(ref, seconds)
        _line(f"d.{sched}.x1.ref", res_ref, wall)
        fields = tuple(res.state._fields)
        _assert_equal_states(res.state, res_ref.state,
                             f"{sched} x{n_chips} vs x1", fields=fields)
        print(f"phase=d.{sched} x{n_chips} == x1: all {len(fields)} state "
              f"fields bit-identical", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase (d)")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} visible",
              file=sys.stderr)
        return 2

    from benchmarks.bench_fleet import _jobs
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    fleet_jobs = _jobs(FLEET["max_jobs"], FLEET["n_servers"])
    if args.chips == 4:
        phase_sharded(FLEET, fleet_jobs, FLEET_SECONDS, 4)
    else:
        phase_fleet(FLEET, fleet_jobs, FLEET_SECONDS)
        phase_paper(1.0, tuple(range(8)))
        phase_service(n_servers=8, n_clients=4, n_chunks=64,
                      chunk=512 * 1024, seed=0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
