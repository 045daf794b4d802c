"""The program's own spans and scopes, from a profiler trace.

:mod:`bench.trace` reduces a traced window to device-busy time and the
harness's call spans.  This module reads what the program itself writes
into the same trace (:mod:`repro.core.spans`):

* the host spans of each engine call (``engine.dispatch``,
  ``engine.device_wait``, ...), on the host's clock, which a lossy device
  trace cannot shorten;
* the ``tick/<phase>`` scope of every device operation of a tick;
* the fused kernel's own events, by its stable name
  (:data:`repro.kernels.tick_step.kernel.KERNEL_NAME`), apart from the
  other events whose text mentions ``tick_step``.

A TPU trace names each operation by its HLO text and carries no op-name
stat, so the scopes come from the compiled program's own text
(:func:`op_names`, e.g. of ``jit(...).lower(...).compile().as_text()``),
which gives each instruction's ``op_name``.  :func:`load` reads an
``.xplane.pb`` like :func:`bench.trace.load`, with each device event's
``op_name`` as a fourth element, so that :func:`bench.trace.reduce_events`
reads its result unchanged; :func:`reduce_spans` reduces it.  The
per-layer readers that use it find the reduction under ``ctx["spans"]``
and report nothing without it.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from bench import trace

try:
    from repro.core import spans as program
    from repro.kernels.tick_step.kernel import KERNEL_NAME
except ImportError:      # a program that writes no spans of its own
    program = None
    KERNEL_NAME = None

#: One instruction of an HLO module's text: its name and its op_name.
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?'
                     r'op_name="([^"]*)"', re.M)


@dataclasses.dataclass
class SpanReduction:
    spans: dict          # host span name -> [seconds] of each, in the window
    calls: list          # [(call_s, device_wait_s)] per harness call span
    op_s: float          # device op time in the calls, summed over devices
    phase_s: dict        # tick phase scope -> device op seconds in the calls
    kernel_s: float      # the named kernel's events in the calls
    n_kernel_events: int

    @property
    def dispatch_s(self) -> list:
        return self.spans.get(program.ENGINE_DISPATCH, [])

    @property
    def device_wait_s(self) -> list:
        return self.spans.get(program.ENGINE_DEVICE_WAIT, [])


def phase_share(ctx, phase: str):
    """The share of the device op time in the traced calls that carries
    the scope ``getattr(repro.core.spans, phase)``, in percent; ``None``
    without a reduction or without op time."""
    red = ctx.get("spans")
    if red is None or red.op_s <= 0:
        return None
    return red.phase_s.get(getattr(program, phase), 0.0) / red.op_s * 100.0


def op_names(hlo_text: str) -> dict:
    """``{instruction: op_name}`` from a compiled program's HLO text; the
    op_name is the path of ``jax.named_scope``s the operation was traced
    under."""
    return dict(_HLO_OP.findall(hlo_text))


def instruction(event_name: str) -> str:
    """The HLO instruction a device event ran: the name its text opens
    with (``%fusion.208 = f32[512] fusion(...)`` -> ``fusion.208``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


#: Operations whose device event spans the operations they run (a loop, a
#: branch, a call): counting them would count their contents twice.
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" = .*?\s([a-z][a-z\-]*)\(")


def opcode(event_name: str) -> str:
    """The HLO opcode of a device event's text (``... fusion(...)``)."""
    m = _OPCODE.search(event_name)
    return m.group(1) if m else ""


def load(path: str, names: dict) -> dict:
    """:func:`bench.trace.load`, with each device event's op_name (from
    ``names``, :func:`op_names` of the traced program; ``""`` where it has
    none) appended."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                [e.name, e.start_ns, e.duration_ns,
                 names.get(instruction(e.name), "")]
                for line in plane.lines if line.name == trace.OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host.extend([e.name, e.start_ns, e.duration_ns]
                        for line in plane.lines for e in line.events)
    return {"devices": devices, "host": host}


def _phase_pattern() -> re.Pattern:
    alts = "|".join(re.escape(p) for p in program.TICK_PHASES)
    return re.compile(rf"(?:^|/)({alts})(?:/|$)")


def _kernel_pattern() -> re.Pattern:
    """The kernel's instruction name (``tick_step_pallas.9``)."""
    return re.compile(rf"{re.escape(KERNEL_NAME)}(?:\.\d+)?")


def reduce_spans(events: dict):
    """The program's spans and scopes in one traced window, in seconds;
    ``None`` where the program writes none."""
    if program is None:
        return None
    host = events["host"]
    windows = [(s, s + d) for n, s, d in host if n == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {trace.WINDOW_SPAN!r} span in the trace")
    w0, w1 = windows[0]
    inside = [(n, s, s + d) for n, s, d in host if w0 <= s and s + d <= w1]
    spans = {}
    for n, s, e in inside:
        if n in program.HOST_SPANS:
            spans.setdefault(n, []).append((e - s) * 1e-9)
    waits = [(s, e) for n, s, e in inside if n == program.ENGINE_DEVICE_WAIT]
    calls = sorted((s, e) for n, s, e in inside if n == trace.CALL_SPAN)
    call_rows = [((ce - cs) * 1e-9,
                  sum(e - s for s, e in waits if cs <= s and e <= ce) * 1e-9)
                 for cs, ce in calls]

    starts = np.array([s for s, _ in calls], float)
    ends = np.array([e for _, e in calls], float)
    phase_of, is_kernel = _phase_pattern(), _kernel_pattern()
    op_ns, phase_ns, kernel_ns, n_kernel = 0.0, {}, 0.0, 0
    for evs in events["devices"].values() if calls else ():
        t0 = np.fromiter((e[1] for e in evs), float, len(evs))
        i = np.searchsorted(starts, t0, side="right") - 1
        in_call = (i >= 0) & (t0 < ends[np.maximum(i, 0)])
        by_op: dict = {}                  # (event name, scope) -> [ns, n]
        for k in np.flatnonzero(in_call).tolist():
            name, _, dur, scope = evs[k]
            acc = by_op.setdefault((name, scope), [0.0, 0])
            acc[0] += dur
            acc[1] += 1
        for (name, scope), (dur, n) in by_op.items():
            if opcode(name) in CONTAINERS:
                continue
            op_ns += dur
            m = phase_of.search(scope)
            if m:
                phase_ns[m.group(1)] = phase_ns.get(m.group(1), 0.0) + dur
            if is_kernel.fullmatch(instruction(name)):
                kernel_ns += dur
                n_kernel += n
    return SpanReduction(
        spans=spans, calls=call_rows, op_s=op_ns * 1e-9,
        phase_s={k: v * 1e-9 for k, v in phase_ns.items()},
        kernel_s=kernel_ns * 1e-9, n_kernel_events=n_kernel)
