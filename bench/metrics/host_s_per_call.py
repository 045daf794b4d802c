"""Facade layer (``repro.api.Experiment``): host seconds per experiment call.

Each call's span (the harness's own annotation) minus the device-busy time
inside it: tracing, lowering, loading the compiled program from the cache,
building the workload and copying results back."""


def read(ctx):
    calls = ctx["reduction"].calls
    if not calls:
        return None
    return sum(span - busy for span, busy in calls) / len(calls)
