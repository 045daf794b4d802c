"""The comparison that decides ``correct``, at sizes a CPU test run holds.

* a sound run of the harness reads correct;
* the control — the reference run in bfloat16 in the program's place —
  reads not correct;
* with the timed path broken underneath the harness, ``correct`` comes out
  false, once for each fault a one-chip cell of this benchmark can have: a
  step that returns its state unchanged, half of the batch left out, an
  answer altered where it is produced.  Every cell here runs on one chip;
  the PR that brings a four-chip cell brings its own fault case with it,
  the exchange between chips left out;
* a configuration is judged by the reference it names: naming
  ``bench/reference.py`` is naming none, a reference that is wrong reads
  not correct, and the composite ``group-then-user-fair`` chain reads
  correct.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference, spec
from bench import spans as bench_spans
from bench.run import run_cell
from repro.core import engine

SMALL = {
    "batch": dict(n_servers=2, n_workers=4, server_bw=22e9, dt=0.001,
                  max_jobs=8, wheel=256, ring_cap=64, sync_ticks=50,
                  sinkhorn_iters=8, bin_ticks=100, fabric_exponent=0.0,
                  policy="user-fair", entry="run_batch", lanes=4,
                  sim_seconds=0.25,
                  jobs={"count": 3, "fields": {
                      "user": {"cycle": [0, 1, 1]}, "size": 1,
                      "procs": {"cycle": [12, 8, 6]},
                      "req_mb": {"cycle": [10, 4, 2]},
                      "start_s": {"cycle": [0.0, 0.2, 0.4],
                                  "scale": "sim_seconds"},
                      "think_s": {"cycle": [0.0, 0.002, 0.001]}}}),
}
SMALL["single"] = dict(SMALL["batch"], entry="run", lanes=1)
# Two groups of users under the paper's composite chain: group 0 holds
# users 0 and 1, group 1 holds user 2.
SMALL["groups"] = dict(
    SMALL["batch"], policy="group-then-user-fair",
    jobs={"count": 4, "fields": dict(SMALL["batch"]["jobs"]["fields"],
                                     user={"cycle": [0, 1, 1, 2]},
                                     group={"cycle": [0, 0, 0, 1]},
                                     procs={"cycle": [12, 8, 6, 10]})})
TRAFFIC = {
    "themis": {"scheduler": "themis", "params": {}},
    "tbf": {"scheduler": "tbf", "params": {
        "mu_ticks": 50, "rate": 0.0, "burst_s": 0.25,
        "ctrl_overhead_s": 0.00055, "headroom": 0.8}},
}


def small_cell(config: str, traffic: str, **overrides) -> spec.Cell:
    metric = {"name": "sim_s_per_s", "unit": "sim-s/s"}
    cfg = dict(SMALL[config], **overrides)
    return spec.Cell(
        name=f"small.{config}.{traffic}", chips=1, config_name=config,
        config=cfg, traffic_name=traffic, traffic=TRAFFIC[traffic],
        reference=spec.reference_path(cfg),
        end_to_end=(metric, {"name": "setup_s", "unit": "s"}), per_layer=())


def run_small(config="batch", traffic="themis", seed=2**31 + 11,
              **overrides):
    lines = []
    res = run_cell(small_cell(config, traffic, **overrides), seed, 0.0,
                   False, compile_cache=False, log=lines.append)
    return res, lines


@pytest.mark.parametrize("config,traffic", [
    ("batch", "themis"), ("batch", "tbf"), ("single", "themis"),
    ("single", "tbf")])
def test_sound_run_is_correct(config, traffic):
    res, lines = run_small(config, traffic)
    assert res["correct"], lines
    assert res["check"] == {"mismatched_elements": {"value": 0, "limit": 0}}
    assert res["failed"] == 0
    assert res["metrics"]["sim_s_per_s"]["value"] > 0
    assert set(res["metrics"]) == {"sim_s_per_s", "setup_s"}


@pytest.mark.parametrize("traffic", ["themis", "tbf"])
def test_control_in_bfloat16_is_not_correct(traffic):
    cfg, tr = SMALL["batch"], TRAFFIC[traffic]
    jobs = spec.make_jobs(cfg)
    seeds = spec.call_seeds(5, 1, cfg["lanes"])
    exact = reference.simulate(cfg, tr, jobs, seeds)
    control = reference.simulate(cfg, tr, jobs, seeds,
                                 float_dtype=jnp.bfloat16)
    ok, numbers = check.verdict(check.mismatches(control, exact))
    assert not ok
    assert numbers["mismatched_elements"][0] > 0


def _unchanged_step(cfg, wl, table, n_bins, shard=None):
    return lambda p, state, _: (state, None)


def _half_batch(real):
    def run_batch(cfg, wl, table, sim_seconds, *, seeds, **kw):
        seeds = list(seeds)
        half = seeds[:len(seeds) // 2] * 2
        return real(cfg, wl, table, sim_seconds, seeds=half, **kw)
    return run_batch


def _altered_answer(real):
    def altered(*a, **kw):
        out = real(*a, **kw)
        st = out["state"]
        completed = np.asarray(st.completed).copy()
        completed.reshape(-1)[0] += 1
        out["state"] = st._replace(completed=completed)
        return out
    return altered


@pytest.mark.parametrize("fault", ["unchanged_step", "half_batch",
                                   "altered_answer"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    if fault == "unchanged_step":
        monkeypatch.setattr(engine, "make_tick", _unchanged_step)
    elif fault == "half_batch":
        monkeypatch.setattr(engine, "run_batch",
                            _half_batch(engine.run_batch))
    else:
        monkeypatch.setattr(engine, "run_batch",
                            _altered_answer(engine.run_batch))
    import repro.api as api

    monkeypatch.setattr(api, "run_batch", engine.run_batch)
    res, lines = run_small("batch", "themis")
    assert not res["correct"], lines
    assert res["check"]["mismatched_elements"]["value"] > 0
    assert res["failed"] > 0


def _verdict(res):
    return {k: res[k] for k in ("correct", "attempted", "failed", "check")}


def test_naming_the_default_reference_is_naming_none():
    named = small_cell("batch", "themis", reference="bench/reference.py")
    assert named.reference == small_cell("batch", "themis").reference
    assert named.reference == spec.DEFAULT_REFERENCE
    none, _ = run_small("batch", "themis")
    explicit, lines = run_small("batch", "themis",
                                reference="bench/reference.py")
    assert explicit["correct"], lines
    assert _verdict(explicit) == _verdict(none)


def test_the_named_reference_judges_the_cell():
    res, lines = run_small("batch", "themis",
                           reference="bench/testdata/reference_altered.py")
    assert not res["correct"], lines
    assert res["check"]["mismatched_elements"]["value"] > 0
    assert res["failed"] > 0


@pytest.mark.parametrize("path", ["src/repro/core/engine.py",
                                  "bench/../README.md", "bench/nowhere.py"])
def test_a_reference_outside_bench_or_missing_is_refused(path):
    with pytest.raises((ValueError, FileNotFoundError)):
        spec.reference_path({"reference": path})


def test_group_then_user_fair_is_correct():
    res, lines = run_small("groups", "themis")
    assert res["correct"], lines
    assert res["check"] == {"mismatched_elements": {"value": 0, "limit": 0}}


@pytest.mark.parametrize("policy,levels", [
    ("job-fair", ("job",)), ("user-then-job-fair", ("user", "job")),
    ("group-then-user-fair", ("group", "user", "job")),
    ("group:fair,user:fair", ("group", "user", "job")),
    ("group,job", ("group", "job"))])
def test_the_reference_parses_fair_chains_itself(policy, levels):
    assert reference.policy_levels(policy) == levels


@pytest.mark.parametrize("policy,error", [
    ("size-fair", NotImplementedError),
    ("group-user-size-fair", NotImplementedError),
    ("user:priority,job", NotImplementedError),
    ("job,user", ValueError), ("fifo", ValueError)])
def test_the_reference_refuses_what_it_does_not_model(policy, error):
    with pytest.raises(error):
        reference.policy_levels(policy)


def test_control_of_the_composite_chain_is_not_correct():
    cfg, tr = SMALL["groups"], TRAFFIC["themis"]
    jobs = spec.make_jobs(cfg)
    seeds = spec.call_seeds(6, 1, cfg["lanes"])
    exact = reference.simulate(cfg, tr, jobs, seeds)
    control = reference.simulate(cfg, tr, jobs, seeds,
                                 float_dtype=jnp.bfloat16)
    ok, numbers = check.verdict(check.mismatches(control, exact))
    assert not ok
    assert numbers["mismatched_elements"][0] > 0


PROGRAM_SPAN_METRICS = ("scan_wall_us_per_tick", "dispatch_s_per_call",
                        "host_self_s_per_call")


def test_a_traced_run_hands_the_readers_spans_and_counters(monkeypatch):
    """On the CPU the trace has no TPU plane, so only the program's host
    spans are there to read; the readers get them, and the traced calls'
    counters, beside the unchanged reduction."""
    real = spec.load_reader
    contexts = []

    def load_reader(name):
        def read(ctx):
            contexts.append(ctx)
            return real(name)(ctx)
        return read

    monkeypatch.setattr(spec, "load_reader", load_reader)
    cell = dataclasses.replace(
        small_cell("batch", "themis"),
        per_layer=tuple({"name": n, "unit": "s"}
                        for n in PROGRAM_SPAN_METRICS))
    lines = []
    res = run_cell(cell, 2**33 + 5, 0.0, True, compile_cache=False,
                   log=lines.append)
    assert res["correct"], lines
    assert set(res["metrics"]) == set(PROGRAM_SPAN_METRICS), lines
    assert all(m["value"] > 0 for m in res["metrics"].values())
    ctx = contexts[0]
    assert ctx["reduction"].window_s > 0
    assert len(ctx["spans"].dispatch_s) == len(ctx["spans"].calls) == 1
    assert [c["lanes"] for c in ctx["counters"]] == [cell.config["lanes"]]
    assert ctx["counters"][0]["ticks"] == reference.n_ticks(cell.config)


@pytest.mark.parametrize("config", ["batch", "single"])
def test_the_lowered_call_carries_every_tick_phase(config):
    """The program the harness lowers for the op names is the window's:
    its compiled text names the tick phases the trace's ops ran under."""
    from bench.run import build_experiment, lower_call
    from repro.core import spans as program

    cell = small_cell(config, "themis")
    seeds = spec.call_seeds(9, 1, cell.config["lanes"])
    text = lower_call(build_experiment(cell), cell.config,
                      seeds).compile().as_text()
    scopes = "\n".join(bench_spans.op_names(text).values())
    assert all(f"/{phase}/" in scopes for phase in program.TICK_PHASES)
