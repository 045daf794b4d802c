"""The control of ``correct``: the cell's reference in bfloat16 in the
program's place, at a cell's own size, read against the float32 reference.

    python3 -m bench.control --workload fig12.themis --seeds 1,2,3

The configurations state float32; the control computes every float of the
simulation one precision lower.  For each seed it simulates the call the
harness's first window call would make and prints the compared number
(``mismatched_elements``) beside its limit; the control has to fail it.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, spec  # noqa: E402


def control_readings(cell: spec.Cell, seeds) -> list[dict]:
    import jax.numpy as jnp

    from bench import reference

    cfg, tr = cell.config, cell.traffic
    ref = spec.load_reference(cell)
    jobs = spec.make_jobs(cfg)
    tick_end = reference.tick_end_rounding(cfg["dt"])
    sim = ref.make_simulator(cfg, tr, jobs, float_dtype=jnp.float32,
                             tick_end=tick_end)
    sim_low = ref.make_simulator(cfg, tr, jobs, float_dtype=jnp.bfloat16,
                                 tick_end=tick_end)
    out = []
    for seed in seeds:
        lanes = spec.call_seeds(seed, 1, int(cfg["lanes"]))
        t0 = time.perf_counter()
        exact = ref.run_lanes(sim, tr, lanes)
        low = ref.run_lanes(sim_low, tr, lanes)
        ok, numbers = check.verdict(check.mismatches(low, exact))
        out.append({"seed": seed, "correct": ok,
                    "seconds": time.perf_counter() - t0,
                    **{k: v for k, (v, _) in numbers.items()}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    cell = spec.resolve_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    for row in control_readings(cell, seeds):
        print(json.dumps({"workload": cell.name, **row,
                          "limit": check.LIMITS["mismatched_elements"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
