"""Shared helpers for the paper-figure benchmarks.

Every simulation routes through the :class:`repro.api.Experiment` facade, so
benchmarks exercise exactly the public entry point users get, and every run
is logged (scheduler, params hash, dropped / idle-worker counters) into
:data:`RUN_LOG` — ``benchmarks.run --json`` embeds that log in the
``BENCH_*.json`` artifact, making each perf-trend point attributable to an
exact configuration.

Variance-at-scale support: :func:`simulate_batch` runs one workload over many
PRNG seeds in a single ``vmap``'d compile (``Experiment.run_batch``), and
:func:`mean_cov` reduces any per-seed metric to the mean ± coefficient of
variation the paper's statistical claims are stated in.
"""
import os
import time

from repro.api import BatchRunResult, Experiment, RunResult
from repro.core import metrics

DEFAULT_SEEDS = tuple(range(8))

#: One entry per simulate/simulate_batch call since the last drain:
#: scheduler, policy, params_hash, dropped, idle_worker_ticks, the resolved
#: tick_impl (``"pallas"`` or ``"ref"``), seconds[, seeds].
RUN_LOG: list[dict] = []


def drain_run_log() -> list[dict]:
    out = list(RUN_LOG)
    RUN_LOG.clear()
    return out


def bench_seconds(default: float = 60.0) -> float:
    """Simulated duration; ``BENCH_SECONDS`` overrides (CI smoke runs ≤5 s)."""
    return float(os.environ.get("BENCH_SECONDS", default))


def bench_seeds(default=DEFAULT_SEEDS) -> tuple:
    """Seed set; ``BENCH_SEEDS=n`` overrides with ``range(n)`` (CI smoke: 2)."""
    n = int(os.environ.get("BENCH_SEEDS", "0"))
    return tuple(range(n)) if n > 0 else tuple(default)


def bench_env() -> dict:
    """The environment block archived in every ``BENCH_*.json`` artifact:
    the ``BENCH_*`` shrink knobs plus the JAX/XLA platform flags — what
    ``benchmarks/trend.py`` folds into each trend series' env key."""
    return {k: os.environ[k] for k in sorted(os.environ)
            if k.startswith(("BENCH_", "XLA_FLAGS"))
            or k == "JAX_PLATFORMS"}


def experiment(scheduler, jobs, *, policy="job-fair", n_servers=1,
               **cfg_kw) -> Experiment:
    """Build the facade spec a benchmark variant runs on.  ``cfg_kw`` mixes
    Experiment-level knobs (``params``, ``n_workers``, ``server_bw``,
    ``seed``) with raw EngineConfig fields (``dt``, ``bin_ticks``, ...);
    keyword binding routes each to the right place."""
    return Experiment(policy=policy, scheduler=scheduler,
                      n_servers=n_servers, **cfg_kw).add_jobs(jobs)


def _log(res: RunResult, seconds, seeds=None) -> None:
    entry = dict(res.counters(), seconds=float(seconds))
    if seeds is not None:
        entry["seeds"] = [int(s) for s in seeds]
    RUN_LOG.append(entry)


def simulate(scheduler, jobs, seconds, *, policy="job-fair", n_servers=1,
             **cfg_kw):
    exp = experiment(scheduler, jobs, policy=policy, n_servers=n_servers,
                     **cfg_kw)
    res = exp.run(seconds)
    _log(res, seconds)
    return res, exp.engine_config()


def simulate_batch(scheduler, jobs, seconds, *, seeds=DEFAULT_SEEDS,
                   policy="job-fair", n_servers=1, **cfg_kw):
    """One compile, ``len(seeds)`` simulations; results carry a seed axis."""
    exp = experiment(scheduler, jobs, policy=policy, n_servers=n_servers,
                     **cfg_kw)
    batch = exp.run_batch(seconds, seeds=seeds)
    _log(batch, seconds, seeds=seeds)
    return batch, exp.engine_config()


def seed_result(batch: BatchRunResult, k: int) -> RunResult:
    """Slice seed ``k`` of a :func:`simulate_batch` result into a per-run
    :class:`RunResult` (every :mod:`repro.core.metrics` helper accepts it)."""
    return batch.seed_result(k)


def per_seed(batch: BatchRunResult) -> list[RunResult]:
    return batch.per_seed()


def seed_metric(batch: BatchRunResult, fn) -> list[float]:
    """Evaluate ``fn(result)`` for every seed of a batch."""
    return batch.seed_metric(fn)


def mean_cov(values) -> tuple[float, float]:
    """Mean and coefficient of variation (std/mean) of a metric across seeds
    (delegates to :func:`repro.core.metrics.mean_cov` — one definition of the
    paper's headline statistic)."""
    return metrics.mean_cov(values)


def sweep(variants: dict[str, dict], seconds, *, seeds=DEFAULT_SEEDS):
    """Config sweep on top of the batch engine.

    ``variants`` maps a label to :func:`simulate_batch` kwargs (``scheduler``,
    ``jobs``, plus any ``policy``/``params``/EngineConfig overrides).  Each
    variant is one compile over all seeds; returns
    ``{label: (batch, cfg, seconds_spent)}``.
    """
    out = {}
    for name, kw in variants.items():
        t0 = time.time()
        batch, cfg = simulate_batch(seconds=seconds, seeds=seeds, **kw)
        out[name] = (batch, cfg, time.time() - t0)
    return out


def fmt_stat(mean: float, cov: float, unit: str = "") -> str:
    return f"{mean:.2f}{unit} cov {cov * 100:.1f}%"


def emit(rows):
    """name,us_per_call,derived CSV rows (assignment format)."""
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")
