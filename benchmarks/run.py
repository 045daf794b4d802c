"""Benchmark harness: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (assignment format); ``--json PATH``
additionally writes the rows as a JSON document so CI can archive per-commit
perf-trajectory artifacts (``BENCH_*.json``).  Each section's document also
carries a ``runs`` block — one entry per simulation with the scheduler, the
scheduler-params hash, and the ``dropped`` / ``idle_worker_ticks`` counters —
so a perf-trend point is attributable to the exact configuration that
produced it.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig12      # one section
    PYTHONPATH=src python -m benchmarks.run fig12 --json BENCH_fig12.json
    PYTHONPATH=src python -m benchmarks.run --list     # sections + schemas

``--workspace DIR`` additionally records every row as a ``bench`` record in
a :mod:`repro.workspace` store (keyed on section/row + attributed
scheduler/params_hash + the ``BENCH_*`` env fingerprint, one buffered
journal append per invocation) — ``benchmarks.trend --workspace`` ingests
those records directly, no artifact files needed.  ``--json`` writes are
atomic (temp-then-rename), so a killed benchmark run never leaves a torn
artifact.
"""
import sys

from repro.compile_cache import enable_compile_cache

from .bench_apps import run_fig13
from .bench_batch import run_batch
from .bench_comparison import run_fig12
from .bench_composite import run_fig9_11
from .bench_fleet import run_fleet
from .bench_kernels import run_micro
from .bench_lambda import run_fig14
from .bench_policies import run_fig8
from .bench_scaling import run_fig7
from .bench_scenarios import run_scen
from .bench_tick import run_kern
from .common import bench_env, drain_run_log, emit

SECTIONS = {
    "batch": run_batch,
    "fig7": run_fig7,
    "fig8": run_fig8,
    "fig9": run_fig9_11,
    "fig12": run_fig12,
    "fig13": run_fig13,
    "fig14": run_fig14,
    "fleet": run_fleet,
    "kern": run_kern,
    "micro": run_micro,
    "scen": run_scen,
}

#: ``--list`` schema: section -> row-name patterns it emits.  ``{...}`` marks
#: the ladder/variant axis; trend-gate direction comes from the row name
#: (see benchmarks/trend.py: ``_vs_``/``budget`` ungated, ``_us_``/``std``/
#: ``wait``/``bsld`` lower-better, ``gbps``/``jain``/``speedup``
#: higher-better).
ROW_SCHEMAS = {
    "batch": ["batch_{preset}_{policy}_meanwait_s",
              "batch_{preset}_{policy}_p95wait_s",
              "batch_{preset}_plan_vs_{baseline}",
              "batch_bridge_{sched}_gbps"],
    "fig7": ["fig7_{sched}_{n}srv_gbps", "fig7_paper_reference"],
    "fig8": ["fig8_{policy}_{job}_gbps", "fig8_{policy}_jain"],
    "fig9": ["fig9_{policy}_{phase}_gbps", "fig11_{policy}_drain_s"],
    "fig12": ["fig12_{sched}_{metric}", "fig12_{sched}_vs_paper"],
    "fig13": ["fig13_{app}_{sched}_s"],
    "fig14": ["fig14_lambda{n}_{metric}"],
    "fleet": ["fleet_run_us_per_tick_x{k}", "fleet_x{k}_vs_x1",
              "fleet_gbps_x1"],
    "kern": ["kern_tick_ref_j{J}", "kern_tick_fused_j{J}",
             "kern_tick_speedup_j{J}", "kern_tick_budget_us_j{J}"],
    "micro": ["micro_{op}_us"],
    "scen": ["scen_{name}_{metric}"],
}


def list_sections() -> None:
    """Print every section, its one-line purpose, and the rows it emits."""
    for name, fn in SECTIONS.items():
        doc = (sys.modules[fn.__module__].__doc__ or "").strip()
        headline = doc.splitlines()[0] if doc else ""
        print(f"{name}: {headline}")
        for pattern in ROW_SCHEMAS.get(name, []):
            print(f"    {pattern}")


def record_to_workspace(root: str, all_rows: dict) -> int:
    """One ``bench`` record per measurement row, flushed as a single
    buffered journal append.  Keys reuse the trend convention: the row's
    scheduler/params_hash attribution plus the env fingerprint, so trend
    series and workspace records line up one-to-one."""
    from repro.workspace import (RunKey, RunRecord, WorkspaceStore,
                                 env_fingerprint)

    from .trend import _attribute, parse_value

    store = WorkspaceStore(root)
    env = env_fingerprint()
    n = 0
    with store.buffered("bench") as buf:
        for section, sec in all_rows.items():
            for row in sec["rows"]:
                run = _attribute(row["name"], sec["runs"])
                key = RunKey(
                    section="bench", name=f"{section}/{row['name']}",
                    scheduler=run.get("scheduler") or "",
                    params_hash=run.get("params_hash") or "",
                    scenario_hash="", env=env)
                buf.put(RunRecord(key=key, payload={
                    "value": parse_value(row["derived"]),
                    "us_per_call": parse_value(row["us_per_call"]),
                    "derived": row["derived"],
                    "dropped": run.get("dropped"),
                    "idle_worker_ticks": run.get("idle_worker_ticks")}))
                n += 1
    return n


def main() -> None:
    argv = sys.argv[1:]
    if "--list" in argv:
        list_sections()
        return
    enable_compile_cache()
    json_path = workspace_root = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_path = argv[i + 1]
        except IndexError:
            raise SystemExit("--json requires a path argument") from None
        argv = argv[:i] + argv[i + 2:]
    if "--workspace" in argv:
        i = argv.index("--workspace")
        try:
            workspace_root = argv[i + 1]
        except IndexError:
            raise SystemExit("--workspace requires a path argument") from None
        argv = argv[:i] + argv[i + 2:]
    want = argv or list(SECTIONS)
    all_rows: dict[str, dict] = {}
    print("name,us_per_call,derived")
    for name in want:
        key = next((k for k in SECTIONS if name.startswith(k)), None)
        if key is None:
            raise SystemExit(f"unknown section {name}; have {list(SECTIONS)}")
        drain_run_log()   # anything stray belongs to no section
        rows = SECTIONS[key]()
        emit(rows)
        all_rows[key] = {
            "rows": [
                {"name": n, "us_per_call": us, "derived": derived}
                for n, us, derived in rows],
            # scheduler + params_hash + dropped/idle counters per simulation
            "runs": drain_run_log(),
        }
    if json_path:
        from repro.workspace import atomic_write_json
        doc = {"sections": all_rows, "env": bench_env()}
        atomic_write_json(json_path, doc)
        print(f"# wrote {json_path}", file=sys.stderr)
    if workspace_root:
        n = record_to_workspace(workspace_root, all_rows)
        print(f"# recorded {n} rows -> workspace {workspace_root}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
