"""Kernel layer (``repro.kernels.tick_step``): the fused tick step's share
of its roofline, in percent.  The least time the chip could take for one
invocation's work (the larger of bytes over HBM bandwidth and flops over
peak, counted from the shapes by ``bench.workcount`` for every lane the
invocation serves) over the measured time per invocation."""

from bench.workcount import roofline_s, tick_step_work


def read(ctx):
    red = ctx["reduction"]
    if not red.n_kernel_events or red.kernel_s <= 0:
        return None
    cfg = ctx["cell"].config
    work = tick_step_work(cfg["n_servers"], cfg["max_jobs"], cfg["n_workers"])
    work = {k: v * ctx["lanes"] for k, v in work.items()}
    bound_s, _ = roofline_s(work, ctx["device_kind"])
    return bound_s / (red.kernel_s / red.n_kernel_events) * 100.0
