"""The comparison that decides ``correct``.

The timed calls' final engine states are compared with the plain reference
(:mod:`bench.reference`) run on the same configuration, population and PRNG
seeds.  The simulation is deterministic, so the comparison is exact: the
number compared is how many elements of the final state (every field, every
lane of every compared call) differ, and its limit is 0.
"""
from __future__ import annotations

import numpy as np

#: The compared number and its limit (an exact comparison has the limit 0).
LIMITS = {"mismatched_elements": 0}


def program_state(state, lanes: int) -> dict:
    """An engine ``EngineState`` as numpy arrays keyed by field name (the
    scheduler accounts as ``aux.<field>``), with a leading lane axis."""
    out = {}
    for name, value in state._asdict().items():
        if name == "aux":
            for k, v in value._asdict().items():
                out[f"aux.{k}"] = np.asarray(v)
        else:
            out[name] = np.asarray(value)
    if lanes == 1 and out["t"].ndim == 0:
        out = {k: v[None] for k, v in out.items()}
    return out


def _differ(a, b) -> np.ndarray:
    """Elementwise: does the program's value differ from the reference's
    (NaN equals NaN)?  Shapes must agree."""
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype.kind == "f" or b.dtype.kind == "f":
        a, b = a.astype(np.float64), b.astype(np.float64)
        return ~((a == b) | (np.isnan(a) & np.isnan(b)))
    return a != b


def mismatches(program: dict, reference: dict) -> dict:
    """Per field: how many elements differ (all of them when the shapes or
    the field sets differ)."""
    out = {}
    for name in sorted(set(program) | set(reference)):
        a, b = program.get(name), reference.get(name)
        if a is None or b is None or np.shape(a) != np.shape(b):
            out[name] = int(max(np.size(a), np.size(b)))
        else:
            out[name] = int(np.count_nonzero(_differ(a, b)))
    return out


def lanes_differing(program: dict, reference: dict, lanes: int) -> int:
    """How many lanes (leading axis) differ from the reference anywhere."""
    bad = np.zeros(lanes, bool)
    for name in set(program) | set(reference):
        a, b = program.get(name), reference.get(name)
        if a is None or b is None or np.shape(a) != np.shape(b):
            return lanes
        bad |= _differ(a, b).reshape(lanes, -1).any(axis=1)
    return int(bad.sum())


def verdict(per_field: dict) -> tuple[bool, dict]:
    """``(correct, {name: (value, limit)})`` from per-field mismatches."""
    numbers = {"mismatched_elements": sum(per_field.values())}
    ok = all(numbers[k] <= LIMITS[k] for k in LIMITS)
    return ok, {k: (numbers[k], LIMITS[k]) for k in LIMITS}
