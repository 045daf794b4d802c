"""Kernel layer (``repro.kernels.tick_step``): device microseconds per fused
tick-step invocation, summed over the kernel's events in the trace.  Cells
whose scheduler has no kernel find no events and report nothing."""


def read(ctx):
    red = ctx["reduction"]
    if not red.n_kernel_events:
        return None
    return red.kernel_s / red.n_kernel_events * 1e6
