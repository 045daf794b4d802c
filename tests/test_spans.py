"""Spans, scopes and counters inside the program (repro.core.spans).

Host spans: a ``jax.profiler`` trace of an experiment call holds the five
spans of :data:`repro.core.spans.HOST_SPANS`, once each, in order, inside
the call.  Device scopes: every phase of the tick names its operations
(``tick/<phase>``), on the single-device and the sharded tick, and the
fused kernel keeps its name.  Counters: ``RunResult.counters()`` counts the
call's ticks, lanes, kernel invocations, policy-chain depth and elements,
and jit traces.  None of it changes a result (the bit-identity tests of
the engine still hold).
"""
import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import pytest

from bench import trace
from repro.api import Experiment
from repro.core import engine, spans
from repro.core.scheduler import available_schedulers, get_scheduler
from repro.kernels.tick_step.kernel import KERNEL_NAME

CALL = "test.call"
SCOPE = re.compile(r"tick/[a-z]+")


def _exp(scheduler="themis", **kw):
    return (Experiment(policy="job-fair", scheduler=scheduler, n_servers=2,
                       n_workers=2, **kw)
            .add_job(user=0, size=1, procs=2, req_mb=4)
            .add_job(user=1, size=1, procs=2, req_mb=4))


def _host_events(tmp_path, calls) -> list:
    """Profile each call under a :data:`CALL` span; the trace's host events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for call in calls:
            with jax.profiler.TraceAnnotation(CALL):
                call()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return trace.load(path)["host"]


def _phases_of(text: str) -> set:
    return set(SCOPE.findall(text))


def _expected_phases(scheduler: str) -> set:
    if get_scheduler(scheduler).uses_segments:
        return set(spans.TICK_PHASES)
    return set(spans.TICK_PHASES) - {spans.TICK_SYNC}   # no lambda-sync


def test_each_call_holds_the_five_host_spans_in_order(tmp_path):
    exp = _exp()
    host = _host_events(tmp_path, [lambda: exp.run(0.01),
                                   lambda: exp.run_batch(0.01, seeds=[1, 2])])
    calls = sorted((s, s + d) for n, s, d in host if n == CALL)
    assert len(calls) == 2
    ours = sorted((s, s + d, n) for n, s, d in host if n in spans.HOST_SPANS)
    assert len(ours) == 2 * len(spans.HOST_SPANS)
    for c0, c1 in calls:
        inside = [(s, e, n) for s, e, n in ours if c0 <= s and e <= c1]
        assert [n for _, _, n in inside] == list(spans.HOST_SPANS)
        for (_, end, _), (start, _, _) in zip(inside, inside[1:]):
            assert end <= start


@pytest.mark.parametrize("scheduler,impl", [("themis", "pallas"),
                                            ("themis", "ref"),
                                            ("tbf", "ref")])
def test_lowered_tick_carries_every_phase_scope(scheduler, impl):
    cfg, wl, table = _exp(scheduler, tick_impl=impl).build()
    assert engine.resolve_tick_impl(cfg, get_scheduler(scheduler)) == impl
    text = engine.lower_run(cfg, wl, table, 0.003).as_text(debug_info=True)
    assert _phases_of(text) == _expected_phases(scheduler)
    # The fused program calls the kernel by its stable name; the scan
    # has no kernel.
    assert (f'"{KERNEL_NAME}/pallas_call"' in text) == (impl == "pallas")


def test_sharded_tick_carries_the_same_scopes():
    code = textwrap.dedent("""
        import re
        from repro.api import Experiment
        from repro.core import engine
        exp = (Experiment(policy="job-fair", scheduler="themis", n_servers=2,
                          n_workers=2, shard_servers=2)
               .add_job(user=0, size=1, procs=2, req_mb=4)
               .add_job(user=1, size=1, procs=2, req_mb=4))
        cfg, wl, table = exp.build()
        text = engine.lower_run(cfg, wl, table, 0.003).as_text(
            debug_info=True)
        assert "all_gather" in text
        print(sorted(set(re.findall(r"tick/[a-z]+", text))))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip() == str(sorted(_expected_phases("themis")))


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_counters_count_the_call(impl):
    exp = _exp(tick_impl=impl)
    for res, lanes in ((exp.run(0.02), 1),
                       (exp.run_batch(0.02, seeds=[1, 2, 3]), 3)):
        c = res.counters()
        assert c["tick_impl"] == impl
        assert (c["ticks"], c["lanes"]) == (20, lanes)
        assert c["kernel_invocations"] == (20 if impl == "pallas" else 0)
        # 2 servers x up to 3 lanes fold into one block of one grid step.
        assert c["kernel_grid_steps"] == (1 if impl == "pallas" else 0)
        # job-fair over 8 slots on each of 2 servers: T^0 is [1, 8].
        assert (c["policy_levels"], c["chain_elements"]) == (1, 16)
        assert c["jit_traces"] == 1
        assert 0 <= c["compile_cache_hits"] <= c["compile_cache_requests"]
    # A lane sliced out of a batch keeps the counters of its call.
    assert res.seed_result(1).counters()["lanes"] == 3


@pytest.mark.parametrize("scheduler", available_schedulers())
def test_lower_run_lowers_every_scheduler(scheduler):
    cfg, wl, table = _exp(scheduler).build()
    text = engine.lower_run(cfg, wl, table, 0.003).as_text(debug_info=True)
    # A scheduler whose bookkeeping is static (fifo) emits no op to scope.
    assert ({spans.TICK_ARRIVALS, spans.TICK_WORKERS, spans.TICK_FINISH}
            <= _phases_of(text) <= _expected_phases(scheduler))
