"""From a profiler trace to the per-layer numbers.

The traced window runs under ``jax.profiler``; the harness marks the window
and each experiment call with host spans (:data:`WINDOW_SPAN`,
:data:`CALL_SPAN`).  From the trace this module takes:

* device busy time: the union of the intervals in which an operation ran
  on a device (the op-level line of each TPU plane), within the window,
  averaged over the chips;
* per call: the call's span and the device-busy time inside it;
* the fused kernel's events, matched by the name the trace gives them
  (:data:`KERNEL_PATTERN`): their count and summed duration;
* the breakdown: the device operations that took the most time, and the
  idle time of the first device by the innermost host span over it.

:func:`load` reads an ``.xplane.pb`` into plain lists of ``[name,
start_ns, duration_ns]``; :func:`reduce_events` reduces them.  The tests
feed the latter small traces kept as JSON beside them.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re

import numpy as np

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
#: Device-op events of the fused tick-step kernel, as the trace names them.
KERNEL_PATTERN = re.compile(r"tick_step")
#: The op-level line of a TPU plane.
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # averaged over devices
    calls: list                   # [(span_s, busy_s)] per call
    kernel_s: float
    n_kernel_events: int
    n_device_events: int
    device_ops: list              # [(name, seconds)] most time first
    idle_gaps: list               # [(host activity, seconds)] most first

    def breakdown(self, n: int = 10) -> dict:
        return {"device_ops": [[k, v] for k, v in self.device_ops[:n]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:n]]}


def load(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}`` from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                [e.name, e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host.extend([e.name, e.start_ns, e.duration_ns]
                        for line in plane.lines for e in line.events)
    return {"devices": devices, "host": host}


def _union(starts: np.ndarray, ends: np.ndarray):
    """Union of intervals as sorted disjoint ``(starts, ends)`` arrays."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:], s.size) - 1
    return s[first], reach[last]


def _covered(us, ue, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the disjoint sorted intervals."""
    a = np.searchsorted(ue, lo, side="right")
    b = np.searchsorted(us, hi, side="left")
    if b <= a:
        return 0.0
    return float(np.sum(np.minimum(ue[a:b], hi) - np.maximum(us[a:b], lo)))


def _innermost(spans):
    """Split the timeline at every span boundary and name each piece by the
    shortest span that covers it: sorted ``[(start, end, name)]``."""
    bounds = sorted({x for s, e, _ in spans for x in (s, e)})
    starts = sorted(spans)
    heap, out, j = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(starts) and starts[j][0] <= a:
            s, e, n = starts[j]
            heapq.heappush(heap, (e - s, e, n))
            j += 1
        while heap and heap[0][1] <= a:     # ended: drop when on top
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][2]))
    return out


def _idle_by_host(us, ue, w0, w1, host) -> dict:
    """Idle time of ``[w0, w1]`` outside the busy union, by the innermost
    host span over it (the window span where no other covers it)."""
    gs = np.concatenate([[w0], ue]).astype(float)
    ge = np.concatenate([us, [w1]]).astype(float)
    keep = ge > gs
    gs, ge = gs[keep], ge[keep]
    label = _innermost([(max(s, w0), min(s + d, w1), n) for n, s, d in host
                        if n != WINDOW_SPAN and s < w1 and s + d > w0])
    out: dict = {}
    for s, e, n in label:
        t = _covered(gs, ge, s, e)
        if t:
            out[n] = out.get(n, 0.0) + t
    rest = float(np.sum(ge - gs)) - sum(out.values())
    if rest > 0:
        out[WINDOW_SPAN] = rest
    return out


def reduce_events(events: dict) -> Reduction:
    """The per-layer numbers of one traced window (times in seconds)."""
    host = events["host"]
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = windows[0]
    calls = sorted((s, s + d) for n, s, d in host if n == CALL_SPAN)

    unions = []
    n_events = 0
    op_time: dict = {}
    op_count: dict = {}
    for evs in events["devices"].values():
        start = np.fromiter((e[1] for e in evs), float, len(evs))
        dur = np.fromiter((e[2] for e in evs), float, len(evs))
        inside = np.flatnonzero((start < w1) & (start + dur > w0))
        n_events += inside.size
        unions.append(_union(np.maximum(start[inside], w0),
                             np.minimum(start[inside] + dur[inside], w1)))
        for i, d in zip(inside.tolist(), dur[inside].tolist()):
            name = evs[i][0]
            op_time[name] = op_time.get(name, 0.0) + d
            op_count[name] = op_count.get(name, 0) + 1
    kernels = [n for n in op_time if KERNEL_PATTERN.search(n)]
    kernel_ns = sum(op_time[n] for n in kernels)
    n_kernel = sum(op_count[n] for n in kernels)
    n_dev = max(len(unions), 1)
    busy = sum(float(np.sum(e - s)) for s, e in unions) / n_dev
    call_rows = [((ce - cs) * 1e-9,
                  sum(_covered(s, e, cs, ce) for s, e in unions) / n_dev
                  * 1e-9) for cs, ce in calls]
    us, ue = unions[0] if unions else (np.zeros(0), np.zeros(0))
    gaps = _idle_by_host(us, ue, w0, w1, host)

    by_time = lambda d: sorted(((k, v * 1e-9) for k, v in d.items()),
                               key=lambda kv: -kv[1])
    return Reduction(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9, calls=call_rows,
        kernel_s=kernel_ns * 1e-9, n_kernel_events=n_kernel,
        n_device_events=n_events, device_ops=by_time(op_time),
        idle_gaps=by_time(gaps))


def trace_file(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {trace_dir}, "
                                f"found {paths}")
    return paths[0]
