"""Kernel layer (``repro.kernels.tick_step``): device microseconds per
invocation of the fused kernel, from its own events only (its stable
``name``), summed over their count.  Cells whose scheduler has no kernel
find no events and report nothing."""


def read(ctx):
    red = ctx.get("spans")
    if not red or not red.n_kernel_events:
        return None
    return red.kernel_s / red.n_kernel_events * 1e6
