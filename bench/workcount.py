"""The work of one fused ``tick_step`` call, counted from its shapes.

Copied from ``repro.roofline.analysis.tick_step_roofline``.  One call at
``[S, J]`` with ``W`` workers streams the share table, the queue counts and
the ``[S, J, W]`` ring window in once and the selections and pops out once:

    bytes = S·J·(3 + W)·4  +  S·W·2·4  +  S·(3·W + 2·J)·4
    flops = S·W·J·12      (a masked prefix sum and segment count per draw)

The count is of the work, whatever implements it; a batch of ``L`` lanes
is ``L`` times the work.
"""
from __future__ import annotations

from .peaks import device_peaks


def tick_step_work(s: int, j: int, w: int, dtype_bytes: int = 4) -> dict:
    """Bytes and flops of one tick-step call at ``[S, J]`` × ``W``."""
    bytes_in = s * j * (3 + w) * dtype_bytes + s * w * 2 * dtype_bytes
    bytes_out = s * (3 * w + 2 * j) * dtype_bytes
    return {"bytes": bytes_in + bytes_out, "flops": s * w * j * 12.0}


def roofline_s(work: dict, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take for ``work``, and which bound
    sets it (``memory`` or ``compute``)."""
    peaks = device_peaks(device_kind)
    memory_s = work["bytes"] / peaks["hbm_bw"]
    compute_s = work["flops"] / peaks["flops"]
    if memory_s >= compute_s:
        return memory_s, "memory"
    return compute_s, "compute"
