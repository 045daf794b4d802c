"""A plain reference that is wrong on purpose: :mod:`bench.reference` with
one more request counted as completed in the first lane's first job.  A
configuration that names this file has to read not correct."""
from bench import reference

make_simulator = reference.make_simulator


def run_lanes(sim, traffic, seeds):
    out = reference.run_lanes(sim, traffic, seeds)
    completed = out["completed"].copy()
    completed.reshape(-1)[0] += 1
    return dict(out, completed=completed)
