"""Time the fused tick-step kernel alone, over block sizes, on the chip.

    python tools/time_tick_blocks.py --rows 128 --jobs 8 --workers 8 \
        --blocks 8,16,32,64,128 --mode themis

For each block size the bare ``pallas_call`` (no padding or slicing
around it) runs ``--calls`` dependent invocations in one jitted
``fori_loop`` under the profiler; the kernel's own device events give its
time per invocation, and the grid steps it took.  Prints one line per
block size and, last, one JSON object.  Needs a TPU: the numbers of the
interpreter on a CPU say nothing about the chip.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.tick_step.kernel import (KERNEL_NAME, block_call,  # noqa: E402
                                            tick_step_grid)


def _inputs(rows: int, j: int, jp: int, w: int, mode: str):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    pad = lambda x: jnp.pad(x, [(0, 0), (0, jp - j)] + [(0, 0)] * (x.ndim - 2))
    ops = [pad(jax.random.uniform(ks[0], (rows, j))),
           pad(jax.random.randint(ks[1], (rows, j), 0, 4)),
           (jax.random.uniform(ks[2], (rows, w)) < 0.9).astype(jnp.int32),
           jax.random.uniform(ks[3], (rows, w))]
    if mode == "fifo":
        ops.append(pad(jnp.cumsum(jax.random.uniform(ks[4], (rows, j, w)),
                                  axis=-1)))
    return ops


def _kernel_events_us(trace_dir: str) -> list:
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out += [e.duration_ns / 1e3 for e in line.events
                        if KERNEL_NAME in e.name]
    return out


def time_block(rows, j, w, mode, block_rows, calls):
    jp = -(-j // 128) * 128
    rows = -(-rows // block_rows) * block_rows
    call = block_call(rows, jp, w, mode=mode, real_j=j, block_rows=block_rows,
                      interpret=False)
    ops = _inputs(rows, j, jp, w, mode)

    @jax.jit
    def chain(*ops):
        def body(_, q):
            _, _, _, qout, pops = call(ops[0], q, *ops[2:])
            return qout + pops           # the counts it started from
        return jax.lax.fori_loop(0, calls, body, ops[1])

    jax.block_until_ready(chain(*ops))   # compile and warm
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        jax.block_until_ready(chain(*ops))
        jax.profiler.stop_trace()
        us = _kernel_events_us(d)
    return {"block_rows": block_rows, "steps": rows // block_rows,
            "events": len(us), "kernel_us": float(np.median(us)),
            "kernel_us_mean": float(np.mean(us))}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--mode", default="themis")
    ap.add_argument("--blocks", default="8,16,32,64,128")
    ap.add_argument("--calls", type=int, default=2000)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("needs a TPU")
    rule = tick_step_grid(a.rows, a.jobs, a.workers, a.mode)
    table = []
    for b in (int(x) for x in a.blocks.split(",")):
        r = time_block(a.rows, a.jobs, a.workers, a.mode, b, a.calls)
        table.append(r)
        print(f"rows={a.rows} J={a.jobs} W={a.workers} mode={a.mode} "
              f"block_rows={b} steps={r['steps']} kernel_us={r['kernel_us']:.3f} "
              f"per_step_us={r['kernel_us'] / r['steps']:.3f} "
              f"events={r['events']}", flush=True)
    print(json.dumps({"rows": a.rows, "jobs": a.jobs, "workers": a.workers,
                      "mode": a.mode, "rule": list(rule), "table": table,
                      "device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
