"""The program's spans and scopes read from a trace (bench.spans), and the
trace reduction of bench.trace pinned on every small trace kept beside it.

``trace_program_spans.json`` holds two calls of three fused ticks: the
program's five host spans inside each call, device events that carry
their ``tick/<phase>`` scope, the named kernel, and a ``tick_step`` event
that is not the kernel.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import pytest

from bench import spans, spec, trace

DATA = Path(__file__).resolve().parent / "testdata"
NS = 1e-9
NEW_READERS = ("scan_wall_us_per_tick", "dispatch_s_per_call",
               "host_self_s_per_call", "tick_kernel_us", "phase_arrivals_pct",
               "phase_sched_pct", "phase_workers_pct", "phase_finish_pct",
               "phase_sync_pct")


def _load(name):
    with open(DATA / name) as f:
        return json.load(f)


def _ctx(events, **extra):
    meta = events["meta"]
    ctx = dict(reduction=trace.reduce_events(events),
               cell=spec.resolve_cell(meta["cell"]), ticks=meta["ticks"],
               lanes=meta["lanes"], device_kind=meta["device_kind"])
    ctx.update(extra)
    return ctx


def _approx(seconds_by_name):
    return [(k, pytest.approx(v * NS)) for k, v in seconds_by_name]


# The reductions of the two older traces, as the unchanged bench.trace
# gives them (nanoseconds).
PINNED = {
    "trace_synthetic.json": dict(
        window_s=100, busy_s=47, calls=[(40, 23), (40, 24)], kernel_s=14,
        n_kernel_events=2, n_device_events=6,
        device_ops=[("fusion.2", 20), ("fusion.1", 15), ("tick_step", 14),
                    ("copy", 3)],
        idle_gaps=[("bench.window", 20), ("bench.call", 19),
                   ("PjitFunction", 10), ("backend_compile", 4)]),
    "trace_two_devices.json": dict(
        window_s=1000, busy_s=300, calls=[(450, 150), (450, 150)],
        kernel_s=120, n_kernel_events=12, n_device_events=24,
        device_ops=[("fusion.3", 480), ("tick_step", 120)],
        idle_gaps=[("bench.call", 460), ("bench.window", 100),
                   ("PjitFunction(_body)", 80)]),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_reduction_of_the_older_traces_is_pinned(name):
    got = dataclasses.asdict(trace.reduce_events(_load(name)))
    want = PINNED[name]
    for key in ("window_s", "busy_s", "kernel_s"):
        assert got[key] == pytest.approx(want[key] * NS), key
    for key in ("n_kernel_events", "n_device_events"):
        assert got[key] == want[key], key
    assert got["calls"] == [(pytest.approx(a * NS), pytest.approx(b * NS))
                            for a, b in want["calls"]]
    for key in ("device_ops", "idle_gaps"):
        assert got[key] == _approx(want[key]), key


def test_the_program_trace_still_reduces_as_before():
    """Device events with a fourth element (the scope) leave bench.trace's
    reduction and the existing readers as they were."""
    red = trace.reduce_events(_load("trace_program_spans.json"))
    # The scan's while event spans its ticks, gaps between them included.
    assert red.busy_s == pytest.approx(560 * NS)
    assert red.calls == [(pytest.approx(450 * NS),
                          pytest.approx(280 * NS))] * 2
    # KERNEL_PATTERN matches the kernel and the slice that names it.
    assert (red.n_kernel_events, red.kernel_s) == (12, pytest.approx(150 * NS))
    # The idle time inside a call goes to the program's innermost span.
    gaps = dict(red.idle_gaps)
    assert gaps["engine.device_wait"] == pytest.approx(120 * NS)
    assert "np.asarray(jax.Array)" not in gaps
    assert sum(gaps.values()) == pytest.approx(440 * NS)


HLO = """\
  %fusion.208 = f32[512]{0:T(512)S(1)} fusion(f32[8,8,512]{2,1,0} %p), kind=kLoop, calls=%fc, metadata={op_name="jit(_body)/while/body/closed_call/tick/finish/add" stack_frame_id=7}
  %tick_step_pallas.9 = (s32[8,8]{1,0}, s32[8,8]{1,0}) custom-call(%pad.441), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(_body)/while/body/closed_call/tick/workers/jit(tick_step)/tick_step_pallas/pallas_call" stack_frame_id=113}
  ROOT %tuple.450 = (u32[1]{0}, u32[1]{0}) tuple(%slice.1799, %slice.1800)
  ROOT %add.4979 = u32[2,1]{1,0} add(%add.4980, %broadcast.2303), metadata={op_name="jit(_body)/while/body/closed_call/tick/workers/add" stack_frame_id=94}
"""


def test_op_names_come_from_the_compiled_text():
    names = spans.op_names(HLO)
    assert names == {
        "fusion.208": "jit(_body)/while/body/closed_call/tick/finish/add",
        "tick_step_pallas.9": "jit(_body)/while/body/closed_call/tick/"
                              "workers/jit(tick_step)/tick_step_pallas/"
                              "pallas_call",
        "add.4979": "jit(_body)/while/body/closed_call/tick/workers/add"}
    event = ("%tick_step_pallas.9 = (s32[8,8]{1,0:T(8,128)S(1)}, s32[8,8]) "
             "custom-call(%pad.441), custom_call_target=\"tpu_custom_call\"")
    assert spans.instruction(event) == "tick_step_pallas.9"
    assert spans.opcode(event) == "custom-call"
    assert spans.opcode("%while.21 = (s32[]{:T(128)}, u32[2]{0:T(128)S(1)}) "
                        "while(%tuple.3), condition=%c, body=%b") == "while"


def test_program_spans_and_scopes_are_reduced():
    red = spans.reduce_spans(_load("trace_program_spans.json"))
    assert set(red.spans) == set(spans.program.HOST_SPANS)
    assert all(len(v) == 2 for v in red.spans.values())
    assert red.device_wait_s == [pytest.approx(340 * NS)] * 2
    assert red.dispatch_s == [pytest.approx(40 * NS)] * 2
    assert red.calls == [(pytest.approx(450 * NS),
                          pytest.approx(340 * NS))] * 2
    # Neither the op outside every call nor the while event around the
    # ticks' own ops is counted.
    assert red.op_s == pytest.approx(480 * NS)
    assert red.phase_s == {
        "tick/arrivals": pytest.approx(60 * NS),
        "tick/sched": pytest.approx(30 * NS),
        "tick/workers": pytest.approx(180 * NS),
        "tick/finish": pytest.approx(60 * NS),
        "tick/sync": pytest.approx(120 * NS)}
    # Only the named kernel's own events: not the slice named after it.
    assert red.n_kernel_events == 6
    assert red.kernel_s == pytest.approx(120 * NS)


def test_every_new_reader_on_the_program_trace():
    events = _load("trace_program_spans.json")
    ctx = _ctx(events, spans=spans.reduce_spans(events))
    values = {name: spec.load_reader(name)(ctx) for name in NEW_READERS}
    assert values == {
        "scan_wall_us_per_tick": pytest.approx(340 / 3 * 1e-3),
        "dispatch_s_per_call": pytest.approx(40 * NS),
        "host_self_s_per_call": pytest.approx(110 * NS),
        "tick_kernel_us": pytest.approx(20 * 1e-3),
        "phase_arrivals_pct": pytest.approx(12.5),
        "phase_sched_pct": pytest.approx(6.25),
        "phase_workers_pct": pytest.approx(37.5),
        "phase_finish_pct": pytest.approx(12.5),
        "phase_sync_pct": pytest.approx(25.0),
    }
    # The scoped shares leave out the ops outside any tick phase.
    phases = sum(v for k, v in values.items() if k.startswith("phase_"))
    assert phases == pytest.approx(100 * (1 - 30 / 480))


def test_new_readers_report_nothing_without_the_program_spans():
    events = _load("trace_two_devices.json")
    ctx = _ctx(events)
    assert all(spec.load_reader(n)(ctx) is None for n in NEW_READERS)


def test_a_scan_cell_has_no_kernel_and_no_sync():
    events = _load("trace_program_spans.json")
    dev = events["devices"]["/device:TPU:0"]
    events["devices"]["/device:TPU:0"] = [
        e for e in dev if not e[0].startswith("%tick_step_pallas")
        and "tick/sync" not in e[3]]
    ctx = _ctx(events, spans=spans.reduce_spans(events))
    assert spec.load_reader("tick_kernel_us")(ctx) is None
    assert spec.load_reader("phase_sync_pct")(ctx) == 0.0
    assert spec.load_reader("phase_workers_pct")(ctx) == pytest.approx(
        60 / 240 * 100)
