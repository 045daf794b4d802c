import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we build abstract inputs (ShapeDtypeStruct — no allocation),
shard them with the production rules, and run ``jit(...).lower().compile()``
on the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh.  Success proves
the distribution config is coherent (shardings consistent, collectives
supported, memory fits); the compiled artifact yields cost_analysis /
memory_analysis / the collective schedule for EXPERIMENTS.md §Dry-run and the
roofline in §Roofline.

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --jobs 6          # full sweep (subprocesses)
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

REPORTS = Path(__file__).resolve().parents[3] / "reports" / "dryrun"


def run_cell(arch: str, shape_name: str, mesh_kind: str, overrides: dict | None = None,
             tag: str = "") -> dict:
    import jax
    import jax.numpy as jnp
    import dataclasses

    from repro.configs.base import get_config, SHAPES
    from repro.configs.inputs import input_specs
    from repro.distributed import sharding as SH
    from repro.distributed.annotate import activate, default_rules
    from repro.launch.mesh import make_production_mesh
    from repro.models import model as M
    from repro.roofline.analysis import analyze_compiled
    from repro.serve.serve_step import make_decode_step, make_prefill_step
    from repro.train import optimizer as O
    from repro.train.train_step import TrainState, make_train_step

    t0 = time.time()
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(len(jax.devices())) if mesh_kind == "multi" else 256

    params_shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                   jax.random.PRNGKey(0))
    p_sh = SH.params_shardings(params_shapes, mesh,
                               fsdp=(shape.kind == "train"))
    specs = input_specs(cfg, shape)
    b_sh = SH.batch_shardings(specs, mesh)

    rules = default_rules(mesh)
    if cfg.sequence_parallel:
        # Megatron-style SP: residual-stream sequence axis over 'model' in
        # the norm/elementwise regions; GSPMD turns the TP all-reduces into
        # reduce-scatter + all-gather pairs and activation residency drops
        # by ~model-axis-size between blocks.
        rules["seq"] = ("model",)
    with mesh, activate(mesh, rules):
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(O.init, params_shapes)
            state_shapes = TrainState(params=params_shapes, opt=opt_shapes)
            state_sh = TrainState(
                params=p_sh,
                opt=O.OptState(step=SH.replicated(mesh),
                               mu=SH.params_shardings(opt_shapes.mu, mesh, fsdp=True),
                               nu=SH.params_shardings(opt_shapes.nu, mesh, fsdp=True)))
            step = make_train_step(cfg, O.OptConfig())
            fn = jax.jit(step, in_shardings=(state_sh, b_sh),
                         donate_argnums=(0,))
            lowered = fn.lower(state_shapes, specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, max_len=shape.seq_len)
            fn = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = fn.lower(params_shapes, specs)
        else:  # decode
            cache_shapes = jax.eval_shape(
                lambda: M.init_caches(cfg, shape.global_batch, shape.seq_len))
            c_sh = SH.caches_shardings(cache_shapes, mesh, shape.global_batch)
            step = make_decode_step(cfg)
            pos_spec = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            fn = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh, SH.replicated(mesh)),
                         donate_argnums=(1,))
            lowered = fn.lower(params_shapes, cache_shapes, specs, pos_spec)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # the production mesh models a v5e pod (repro.launch.mesh)
    report = analyze_compiled(cfg, shape, compiled, chips=chips,
                              device_kind="TPU v5 lite")
    report.update({
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "overrides": overrides or {}, "tag": tag,
    })
    return report


def cell_name(arch, shape, mesh, tag=""):
    suffix = f"__{tag}" if tag else ""
    return f"{arch}__{shape}__{mesh}{suffix}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", default="",
                    help="JSON dict of ModelConfig overrides (perf experiments)")
    args = ap.parse_args()
    REPORTS.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.configs.base import cells, list_archs
        todo = []
        for arch in list_archs():
            for shape in cells(arch):
                for mesh in ["single", "multi"]:
                    out = REPORTS / f"{cell_name(arch, shape, mesh)}.json"
                    if args.force or not out.exists():
                        todo.append((arch, shape, mesh))
        print(f"{len(todo)} cells to run, {args.jobs} at a time", flush=True)
        procs: list[tuple] = []
        failed = []
        while todo or procs:
            while todo and len(procs) < args.jobs:
                arch, shape, mesh = todo.pop(0)
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh]
                if args.force:
                    cmd.append("--force")
                p = subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                                     stderr=subprocess.PIPE)
                procs.append((p, (arch, shape, mesh)))
                print(f"launch {arch} {shape} {mesh}", flush=True)
            done = [t for t in procs if t[0].poll() is not None]
            for p, cell in done:
                procs.remove((p, cell))
                if p.returncode != 0:
                    failed.append(cell)
                    err = p.stderr.read().decode()[-2000:]
                    print(f"FAIL {cell}: {err}", flush=True)
                else:
                    print(f"done {cell}", flush=True)
            time.sleep(2)
        print(f"sweep complete; {len(failed)} failures: {failed}", flush=True)
        sys.exit(1 if failed else 0)

    overrides = json.loads(args.override) if args.override else None
    name = cell_name(args.arch, args.shape, args.mesh, args.tag)
    out = REPORTS / f"{name}.json"
    if out.exists() and not args.force and not args.tag:
        print(f"cached: {out}")
        return
    try:
        report = run_cell(args.arch, args.shape, args.mesh, overrides, args.tag)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    out.write_text(json.dumps(report, indent=1))
    print(json.dumps({k: report[k] for k in
                      ["arch", "shape", "mesh", "compute_s", "memory_s",
                       "collective_s", "bottleneck", "compile_s"]}, indent=1))
    # headline numbers required by the assignment
    print("memory_analysis:", report.get("memory_analysis"))
    print("cost_analysis flops:", report.get("flops_per_device"))


if __name__ == "__main__":
    main()
