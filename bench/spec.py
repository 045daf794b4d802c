"""Find a cell's files by name and build its job population.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  A
configuration is ``bench/configs/<file>.json`` (the deployment: geometry,
sharing policy, engine options, the job population, how long and how wide
one experiment call is, and optionally ``"reference"``, the path of the
plain reference that judges it); a traffic mix is
``bench/traffic/<traffic>.json`` (the scheduler that serves the population
and its parameters); a per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, or a deployment with a
mechanism of its own, therefore adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
BENCHMARK_FILE = ROOT / "BENCHMARK.json"
TRAFFIC_DIR = BENCH_DIR / "traffic"
METRICS_DIR = BENCH_DIR / "metrics"
#: The reference of a configuration that names none.
DEFAULT_REFERENCE = BENCH_DIR / "reference.py"


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    reference: Path         # the plain reference that judges the cell
    end_to_end: tuple       # metric entries of BENCHMARK.json
    per_layer: tuple        # metric entries that apply to this cell


def load_benchmark(path: Path = BENCHMARK_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str) -> Path:
    return TRAFFIC_DIR / f"{name}.json"


def metric_path(name: str) -> Path:
    return METRICS_DIR / f"{name}.py"


def reference_path(config: dict) -> Path:
    """The file of the configuration's plain reference: its
    ``"reference"`` (a path from the repository root that lies under
    ``bench/``), else :data:`DEFAULT_REFERENCE`."""
    if "reference" not in config:
        return DEFAULT_REFERENCE
    path = (ROOT / config["reference"]).resolve()
    if not path.is_relative_to(BENCH_DIR) or path.suffix != ".py":
        raise ValueError(f"reference {config['reference']!r} is not a .py "
                         f"file under {BENCH_DIR.name}/")
    if not path.is_file():
        raise FileNotFoundError(f"reference: no file {path}")
    return path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, bench: dict | None = None) -> Cell:
    """The cell called ``name``, its configuration and traffic loaded."""
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = _load_json(ROOT / centry["file"], f"config {w['config']}")
    traffic = _load_json(traffic_path(w["traffic"]), f"traffic {w['traffic']}")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        reference=reference_path(config),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def _load_module(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return _load_module(f"bench.metrics.{metric.replace('.', '_')}",
                        metric_path(metric)).read


def load_reference(cell: Cell):
    """The module of the cell's plain reference, loaded from its file: it
    has ``make_simulator`` and ``run_lanes`` (the contract is in
    :mod:`bench.reference`'s docstring)."""
    rel = cell.reference.relative_to(ROOT).with_suffix("")
    return _load_module("_".join(rel.parts), cell.reference)


# -- the job population ------------------------------------------------------

def _rule_value(rule, i: int, sim_seconds: float):
    """One field of job ``i``: a constant, or ``{"cycle": [...]}`` (the
    ``i mod len`` entry, optionally ``"scale": "sim_seconds"``)."""
    if isinstance(rule, (int, float)):
        return rule
    v = rule["cycle"][i % len(rule["cycle"])]
    if rule.get("scale") == "sim_seconds":
        v = v * sim_seconds
    return v


def make_jobs(config: dict) -> list[dict]:
    """The configuration's job population as engine job-spec dicts.

    The population is fixed by the configuration alone (it is compiled
    into the engine's program); ``--seed`` never changes it."""
    block = config["jobs"]
    fields = block["fields"]
    sim_s = float(config["sim_seconds"])
    return [{k: _rule_value(r, i, sim_s) for k, r in fields.items()}
            for i in range(int(block["count"]))]


def call_seeds(seed: int, call: int, lanes: int) -> tuple[int, ...]:
    """The PRNG seeds of one experiment call: call ``0`` is the warm-up,
    window call ``i`` is ``i + 1``.  Any whole ``seed`` (also beyond 32
    bits, also negative) maps to uint32 lanes the engine takes as they
    are."""
    ss = np.random.SeedSequence([seed % 2**64, call])
    return tuple(int(x) for x in ss.generate_state(lanes, np.uint32))
