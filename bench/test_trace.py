"""The trace-to-metrics reduction, on small traces kept beside it."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import spans, spec, trace

DATA = Path(__file__).resolve().parent / "testdata"


def _load(name):
    with open(DATA / name) as f:
        return json.load(f)


def test_hand_made_trace():
    red = trace.reduce_events(_load("trace_synthetic.json"))
    ns = 1e-9
    assert red.window_s == pytest.approx(100 * ns)
    # union of [20, 40], [45, 48], [70, 94]; the op at 200 is outside
    assert red.busy_s == pytest.approx(47 * ns)
    assert red.calls == [(pytest.approx(40 * ns), pytest.approx(23 * ns)),
                         (pytest.approx(40 * ns), pytest.approx(24 * ns))]
    assert red.n_kernel_events == 2
    assert red.kernel_s == pytest.approx(14 * ns)
    assert red.n_device_events == 6
    assert [k for k, _ in red.device_ops] == ["fusion.2", "fusion.1",
                                             "tick_step", "copy"]
    gaps = dict(red.idle_gaps)
    assert gaps == {"bench.window": pytest.approx(20 * ns),
                    "bench.call": pytest.approx(19 * ns),
                    "PjitFunction": pytest.approx(10 * ns),
                    "backend_compile": pytest.approx(4 * ns)}
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)
    out = red.breakdown()
    assert set(out) == {"device_ops", "idle_gaps"}
    assert len(out["device_ops"]) <= 10


def _with_program_spans(events):
    """The two-device trace as the program writes it: each call opens with
    ``engine.dispatch`` (``[0, 45]``, around the jit's own span) and then
    ``engine.device_wait`` (to ``440``); each ``tick_step`` event is the
    named kernel under ``tick/workers``, each ``fusion.3`` runs under
    ``tick/arrivals`` on device 0 and ``tick/sched`` on device 1."""
    scope = "jit(_body)/while/body/closed_call/tick/{}/op"
    fusion_phase = {"/device:TPU:0": "arrivals", "/device:TPU:1": "sched"}
    devices = {}
    for plane, evs in events["devices"].items():
        devices[plane] = [
            ["%tick_step_pallas.9 = (s32[8,8]) custom-call(f32[8,128] %p)",
             s, d, scope.format("workers") + "/tick_step_pallas"]
            if name == "tick_step" else
            [f"%{name} = f32[8,8] fusion(f32[8,8] %p)", s, d,
             scope.format(fusion_phase[plane])]
            for name, s, d in evs]
    host = list(events["host"])
    for call0 in (0, 500):
        host += [["engine.dispatch", call0, 45],
                 ["engine.device_wait", call0 + 45, 395]]
    return dict(events, devices=devices, host=host)


def test_two_device_trace_reads_every_metric():
    events = _with_program_spans(_load("trace_two_devices.json"))
    red = trace.reduce_events(events)
    meta = events["meta"]
    ns = 1e-9
    # per call: device 0 busy 3 x (50 + 10), device 1 3 x (30 + 10)
    assert red.busy_s == pytest.approx((360 + 240) / 2 * ns)
    assert red.calls == [(pytest.approx(450 * ns), pytest.approx(150 * ns))] * 2
    assert red.n_kernel_events == 2 * meta["calls"] * meta["ticks"]
    cell = spec.resolve_cell(meta["cell"])
    ctx = dict(reduction=red, spans=spans.reduce_spans(events), cell=cell,
               ticks=meta["ticks"], lanes=meta["lanes"],
               device_kind=meta["device_kind"])
    values = {m["name"]: spec.load_reader(m["name"])(ctx)
              for m in cell.per_layer}
    assert values == {
        "host_s_per_call": pytest.approx(300 * ns),
        "device_us_per_tick": pytest.approx(300 / 6 * 1e-3),
        "tick_step_us": pytest.approx(10 * 1e-3),
        # 8 lanes x 576 bytes at 819 GB/s, over 10 ns
        "tick_step_roofline": pytest.approx(8 * 576 / 819e9 / 10e-9 * 100),
        "device_idle_share": pytest.approx(70.0),
        # the program's spans: 395 ns of device wait per call of 3 ticks
        "scan_wall_us_per_tick": pytest.approx(395 / 3 * 1e-3),
        "dispatch_s_per_call": pytest.approx(45 * ns),
        "host_self_s_per_call": pytest.approx((450 - 395) * ns),
        # op time in the calls: 360 ns on device 0, 240 on device 1
        "phase_arrivals_pct": pytest.approx(300 / 600 * 100),
        "phase_sched_pct": pytest.approx(180 / 600 * 100),
        "phase_workers_pct": pytest.approx(120 / 600 * 100),
        "phase_finish_pct": 0.0,
        "phase_sync_pct": 0.0,
        "tick_kernel_us": pytest.approx(10 * 1e-3),
    }
    assert 0 < values["tick_step_roofline"] <= 100
    gaps = dict(red.idle_gaps)
    # idle on device 0: 1000 - 360, of which 2 x 40 under the jit's span
    # and 2 x 5 under the rest of engine.dispatch
    assert gaps["PjitFunction(_body)"] == pytest.approx(80 * ns)
    assert gaps["engine.dispatch"] == pytest.approx(10 * ns)
    assert gaps["engine.device_wait"] == pytest.approx(430 * ns)
    assert sum(gaps.values()) == pytest.approx(640 * ns)


def test_every_cell_reads_its_per_layer_metrics_from_the_program_trace():
    """Each per-layer entry of ``BENCHMARK.json`` applies to a cell whose
    scheduler gives its reader something to read: the named kernel's
    metric only where the traffic runs the fused kernel."""
    events = _with_program_spans(_load("trace_two_devices.json"))
    no_kernel = dict(events, devices={
        plane: [e for e in evs if "tick_step_pallas" not in e[0]]
        for plane, evs in events["devices"].items()})
    for name in ("fig12.themis", "fleet128.themis", "fig12.tbf",
                 "fleet128.tbf"):
        cell = spec.resolve_cell(name)
        ev = events if cell.traffic["scheduler"] == "themis" else no_kernel
        ctx = dict(reduction=trace.reduce_events(ev),
                   spans=spans.reduce_spans(ev), cell=cell, ticks=3, lanes=1,
                   device_kind="TPU v5 lite")
        got = {m["name"] for m in cell.per_layer
               if spec.load_reader(m["name"])(ctx) is not None}
        assert got == {m["name"] for m in cell.per_layer}, name


def test_a_trace_without_the_window_span_is_refused():
    events = _load("trace_synthetic.json")
    events["host"] = [h for h in events["host"] if h[0] != trace.WINDOW_SPAN]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_events(events)


def test_readers_find_nothing_to_read_without_kernel_events():
    events = _load("trace_synthetic.json")
    dev = events["devices"]["/device:TPU:0"]
    events["devices"]["/device:TPU:0"] = [e for e in dev
                                          if e[0] != "tick_step"]
    red = trace.reduce_events(events)
    cell = spec.resolve_cell("fig12.tbf")
    ctx = dict(reduction=red, cell=cell, ticks=10, lanes=8, n_calls=2,
               device_kind="TPU v5 lite")
    for name in ("tick_step_us", "tick_step_roofline"):
        assert spec.load_reader(name)(ctx) is None
    assert spec.load_reader("device_idle_share")(ctx) > 0
