"""The multi-tenant 128-node deployment (``themisio-tenants128``) on the CPU.

* its file loads to the population it states: 64 jobs of 3 groups and 12
  users, striped so that every node serves 16 to 22 jobs with at most 8
  clients per (node, job), and a re-arrival never outruns the wheel;
* the policy-chain counters read what the chain builds in each cell;
* a small copy of it (8 nodes, 16 slots, non-dyadic shares, stripes 2 to
  8 wide, staggered windows) reads correct through the harness, and its
  bfloat16 control does not;
* the cell's two readers report nothing where the program or the trace
  has nothing for them, and the tick/sched reader divides by the ticks a
  trace cut at its event cap kept.
"""
from __future__ import annotations

import dataclasses
import math

import pytest

from bench import control, reference, spans, spec, trace
from bench.run import build_experiment, run_cell
from bench.test_trace import _load, _with_program_spans
from repro.core import engine
from repro.core import spans as program
from repro.core.scheduler import get_scheduler

CELL = "tenants128.themis"


@pytest.fixture(scope="module")
def cell():
    return spec.resolve_cell(CELL)


def test_the_file_loads_the_stated_population(cell):
    cfg = cell.config
    jobs = spec.make_jobs(cfg)
    assert len(jobs) == cfg["max_jobs"] == 64
    assert {j["group"] for j in jobs} == {0, 1, 2}
    assert len({(j["group"], j["user"]) for j in jobs}) == 12
    assert cell.reference == spec.DEFAULT_REFERENCE
    arr = reference.lower_jobs(cfg, jobs)
    procs = arr["procs"]                              # [node, job]
    assert procs.max() == 8 <= cfg["ring_cap"]
    assert set(procs[procs > 0].tolist()) == {8}
    per_node = (procs > 0).sum(axis=1)
    assert (per_node.min(), per_node.max()) == (16, 22)
    assert sorted({len(j["servers"]) for j in jobs}) == [8, 16, 32, 64, 128]


def test_the_largest_re_arrival_offset_is_below_the_wheel(cell):
    cfg = cell.config
    worker_bw = (cfg["server_bw"] / cfg["n_workers"]
                 * cfg["n_servers"] ** -cfg["fabric_exponent"])
    req = max(j["req_mb"] for j in spec.make_jobs(cfg)) * 1e6
    offset = math.ceil((cfg["dt"] + req / worker_bw) / cfg["dt"])
    assert offset == 7
    assert 36 * offset < cfg["wheel"]


@pytest.mark.parametrize("name,levels,elements", [
    ("fig12.themis", 1, 8), ("fleet128.themis", 1, 1024),
    ("fig12.tbf", 0, 0), ("fleet128.tbf", 0, 0),
    (CELL, 3, 128 * (64 + 2 * 64 ** 3))])
def test_chain_counters_of_every_cell(name, levels, elements):
    cfg = build_experiment(spec.resolve_cell(name)).engine_config()
    c = engine._call_counters(engine._call_marks(), cfg,
                              get_scheduler(cfg.scheduler), 1, 1, "ref")
    assert (c["policy_levels"], c["chain_elements"]) == (levels, elements)


def small_copy(cell) -> spec.Cell:
    """The deployment at 8 nodes and 16 slots for 0.05 s: group 0 holds
    users 0 (6 jobs) and 1 (2), group 1 users 2 (3) and 3 (2), group 2
    users 4 (2) and 5 (1), so shares run from 1/36 to 1/6."""
    widths = [2, 4, 8, 2, 4, 8, 2, 4]
    cfg = dict(
        cell.config, n_servers=8, max_jobs=16, n_workers=4, sync_ticks=50,
        bin_ticks=50, sim_seconds=0.05,
        jobs={"count": 16, "fields": {
            "group": {"cycle": [0] * 8 + [1] * 5 + [2] * 3},
            "user": {"cycle": [0] * 6 + [1] * 2 + [2] * 3 + [3] * 2
                     + [4] * 2 + [5]},
            "size": {"cycle": widths},
            "procs": {"cycle": [8 * w for w in widths]},
            "req_mb": 1,
            "start_s": {"cycle": [0.0, 0.0, 0.25, 0.5],
                        "scale": "sim_seconds"},
            "end_s": {"cycle": [1.0, 0.5, 0.75, 1.0],
                      "scale": "sim_seconds"},
            "servers": {"cycle": [[(3 * i + k) % 8
                                   for k in range(widths[i % 8])]
                                  for i in range(16)]}}})
    return dataclasses.replace(cell, name="small.tenants", config=cfg,
                               per_layer=())


def test_a_small_copy_reads_correct(cell):
    small = small_copy(cell)
    lines = []
    res = run_cell(small, 2**33 + 17, 0.0, False, compile_cache=False,
                   log=lines.append)
    assert res["correct"], lines
    assert res["check"] == {"mismatched_elements": {"value": 0, "limit": 0}}
    assert res["failed"] == 0
    assert "dropped=0 " in lines[1]


def test_the_control_of_the_small_copy_is_not_correct(cell):
    (row,) = control.control_readings(small_copy(cell), [2**33 + 17])
    assert not row["correct"]
    assert row["mismatched_elements"] > 0


def _ctx(events, counters, cell):
    return dict(reduction=trace.reduce_events(events),
                spans=spans.reduce_spans(events), counters=counters,
                cell=cell, ticks=3, lanes=1, device_kind="TPU v5 lite")


def test_the_readers_read_the_phase_and_the_counter(cell):
    events = _with_program_spans(_load("trace_two_devices.json"))
    ctx = _ctx(events, [{"chain_elements": 67_117_056}] * 2, cell)
    # 180 ns under tick/sched over 12 kernel events: 2 devices x 2 calls x
    # 3 ticks
    assert spec.load_reader("sched_us_per_tick")(ctx) == pytest.approx(
        180 / 12 * 1e-3)
    assert spec.load_reader("chain_melem_per_tick")(ctx) == 67.117056


def test_a_trace_cut_at_its_cap_reads_the_ticks_it_kept(cell):
    """A trace that hit its event cap keeps the window's first events and
    drops the rest; the reader divides by the ticks the kept events cover
    (here the first call's), so it reads what the whole trace reads."""
    events = _with_program_spans(_load("trace_two_devices.json"))
    cut = dict(events, devices={
        plane: [e for e in evs if e[1] < 500]
        for plane, evs in events["devices"].items()})
    whole = spec.load_reader("sched_us_per_tick")(_ctx(events, [], cell))
    kept = _ctx(cut, [], cell)
    assert kept["spans"].n_kernel_events == 6
    assert kept["spans"].phase_s[program.TICK_SCHED] == pytest.approx(90e-9)
    assert spec.load_reader("sched_us_per_tick")(kept) == pytest.approx(
        whole)


def test_the_readers_report_nothing_without_the_phase_or_the_counter(cell):
    events = _with_program_spans(_load("trace_two_devices.json"))
    no_sched = dict(events, devices={
        plane: [e for e in evs if "/tick/sched/" not in e[3]]
        for plane, evs in events["devices"].items()})
    ctx = _ctx(no_sched, [{"lanes": 1}], cell)
    assert spec.load_reader("sched_us_per_tick")(ctx) is None
    assert spec.load_reader("chain_melem_per_tick")(ctx) is None
    # The scan path runs no named kernel: no kernel events to count ticks.
    no_kernel = dict(events, devices={
        plane: [e for e in evs if "tick_step_pallas" not in e[0]]
        for plane, evs in events["devices"].items()})
    assert spec.load_reader("sched_us_per_tick")(
        _ctx(no_kernel, [], cell)) is None
    bare = dict(reduction=trace.reduce_events(events), cell=cell, ticks=3)
    for name in ("sched_us_per_tick", "chain_melem_per_tick"):
        assert spec.load_reader(name)(bare) is None


def test_the_cell_lists_its_readers(cell):
    names = {m["name"] for m in cell.per_layer}
    assert {"sched_us_per_tick", "chain_melem_per_tick"} <= names
    other = spec.resolve_cell("fleet128.themis")
    assert not {"sched_us_per_tick", "chain_melem_per_tick"} & {
        m["name"] for m in other.per_layer}


def test_geometry_and_wheel_bytes_are_fleet128s(cell):
    """A later change to the kernel or the wheel is judged on both cells."""
    a, b = cell.config, spec.resolve_cell("fleet128.themis").config
    for key in ("n_servers", "n_workers", "server_bw", "fabric_exponent",
                "dt", "ring_cap", "sync_ticks", "sinkhorn_iters", "entry"):
        assert a[key] == b[key], key
    assert (a["n_servers"] * a["max_jobs"] * a["wheel"]
            == b["n_servers"] * b["max_jobs"] * b["wheel"])
