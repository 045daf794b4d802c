"""Engine scan layer (``repro.core.engine``): device microseconds per
simulated tick under the ``tick/sched`` scope (scheduler bookkeeping and the
per-tick share table: for themis, the per-server policy chain).

The fused path runs the named kernel once per tick on each device, so the
traced calls' kernel events count the ticks whose events the trace kept:
the ``tick/sched`` op time is divided by them, not by calls times ticks.  A
trace that hit its event cap keeps the early ticks of the window and drops
the rest, so the ratio holds where a division by every traced tick would
read low.  ``None`` on the scan path (no kernel events) and where the trace
has no such phase."""

from bench.spans import program


def read(ctx):
    red = ctx.get("spans")
    if red is None or program is None or not red.n_kernel_events:
        return None
    sched_s = red.phase_s.get(program.TICK_SCHED)
    if not sched_s:
        return None
    return sched_s / red.n_kernel_events * 1e6
