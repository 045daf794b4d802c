"""The comparison that decides ``correct``, at sizes a CPU test run holds.

* a sound run of the harness reads correct;
* the control — the reference run in bfloat16 in the program's place —
  reads not correct;
* with the timed path broken underneath the harness, ``correct`` comes out
  false, once for each fault a one-chip cell of this benchmark can have: a
  step that returns its state unchanged, half of the batch left out, an
  answer altered where it is produced.  (No cell spans chips, so there is
  no exchange between chips to leave out.)
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference, spec
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
TRAFFIC = {
    "themis": {"scheduler": "themis", "params": {}},
    "tbf": {"scheduler": "tbf", "params": {
        "mu_ticks": 50, "rate": 0.0, "burst_s": 0.25,
        "ctrl_overhead_s": 0.00055, "headroom": 0.8}},
}


def small_cell(config: str, traffic: str) -> spec.Cell:
    metric = {"name": "sim_s_per_s", "unit": "sim-s/s"}
    return spec.Cell(
        name=f"small.{config}.{traffic}", chips=1, config_name=config,
        config=SMALL[config], traffic_name=traffic, traffic=TRAFFIC[traffic],
        end_to_end=(metric, {"name": "setup_s", "unit": "s"}), per_layer=())


def run_small(config="batch", traffic="themis", seed=2**31 + 11):
    lines = []
    res = run_cell(small_cell(config, traffic), seed, 0.0, False,
                   compile_cache=False, log=lines.append)
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
