"""Facade layer (``repro.api.Experiment``): host seconds per call that are
not spent waiting for the device: each call's span (the harness's
annotation) minus the program's ``engine.device_wait`` span inside it."""


def read(ctx):
    red = ctx.get("spans")
    if red is None or not red.calls or not red.device_wait_s:
        return None
    return sum(call - wait for call, wait in red.calls) / len(red.calls)
