"""Facade layer (``repro.api.Experiment``): host seconds per call in the
program's ``engine.dispatch`` span (trace, lower, load or compile, and
enqueue the fresh jitted program)."""


def read(ctx):
    red = ctx.get("spans")
    if red is None or not red.dispatch_s:
        return None
    return sum(red.dispatch_s) / len(red.dispatch_s)
