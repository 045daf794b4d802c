"""Policy chain layer (``repro.core.policy``): millions of transition-matrix
elements the per-server policy chain builds per simulated tick of one lane,
over all servers, from the traced calls' ``chain_elements`` counter
(``RunResult.counters()``).  ``None`` where the program has no such
counter."""


def read(ctx):
    counts = [c.get("chain_elements") for c in ctx.get("counters") or ()]
    if not counts or None in counts:
        return None
    return max(counts) / 1e6
