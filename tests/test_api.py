"""The ``repro.api`` facade: one Experiment spec drives the jitted engine and
the burst-buffer service, for every registered scheduler, with identical
share tables — plus the structured :class:`RunResult` contract."""
import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import BatchRunResult, Experiment, RunResult
from repro.core import (EngineConfig, TbfParams, available_schedulers,
                        get_scheduler, make_workload, run)
from repro.core.scheduler import TickView

_FOCUS = os.environ.get("REPRO_SCHEDULER")
SCHEDULERS = (_FOCUS,) if _FOCUS else available_schedulers()

TWO_JOBS = dict(size=1, procs=8, req_mb=10, end_s=2)


def two_job_exp(sched, **kw):
    return (Experiment(policy="job-fair", scheduler=sched, n_workers=4, **kw)
            .add_job(user=0, **TWO_JOBS)
            .add_job(user=1, **TWO_JOBS))


class TestBuilder:
    def test_unknown_scheduler_fails_fast(self):
        with pytest.raises(ValueError, match="unknown scheduler"):
            Experiment(scheduler="nope")

    def test_params_type_checked_at_construction(self):
        with pytest.raises(TypeError, match="GiftParams"):
            Experiment(scheduler="gift", params=TbfParams())

    def test_sibling_bucket_schema_rejected(self):
        """AdaptbfParams and TbfParams share the bucket base; accepting one
        for the other's scheduler would run it with the wrong calibrated
        values unnoticed."""
        from repro.core import AdaptbfParams
        with pytest.raises(TypeError, match="exactly TbfParams"):
            Experiment(scheduler="tbf", params=AdaptbfParams())

    def test_serve_honors_engine_kw(self):
        """Same spec, both planes: engine timing overrides (dt, sync_ticks)
        must reach the service's config, not just run()'s."""
        exp = two_job_exp("gift", dt=2e-4, sync_ticks=100)
        svc = exp.serve()
        assert svc.cluster.cfg.dt == 2e-4
        assert svc.cluster.cfg.sync_ticks == 100
        # the service's lambda-sync cadence follows sync_ticks x dt, so both
        # planes sync segments at the same virtual times
        assert svc.cluster.lam_s == pytest.approx(100 * 2e-4)
        assert exp.serve(lam_s=0.25).cluster.lam_s == 0.25
        sobj = exp.sched
        svc_cfg, eng_cfg = svc.cluster.cfg, exp.engine_config()
        assert (sobj.mu_s(sobj.params(svc_cfg), svc_cfg.dt)
                == sobj.mu_s(sobj.params(eng_cfg), eng_cfg.dt))

    def test_run_without_jobs_raises(self):
        with pytest.raises(ValueError, match="add_job"):
            Experiment().run(1.0)

    def test_arrivals_updates_one_or_all_jobs(self):
        exp = (Experiment().add_job(user=0).add_job(user=1)
               .arrivals(start_s=1.0).arrivals(job=1, end_s=5.0))
        assert [j["start_s"] for j in exp.jobs] == [1.0, 1.0]
        assert exp.jobs[1]["end_s"] == 5.0 and "end_s" not in exp.jobs[0]

    def test_arrivals_before_add_job_raises(self):
        with pytest.raises(ValueError, match="add_job"):
            Experiment().arrivals(start_s=1.0)
        with pytest.raises(ValueError, match="add_job"):
            Experiment().arrivals(job=0, start_s=1.0)

    def test_segment_scheduler_defaults_policy_on_both_planes(self):
        """policy=None with a segment scheduler must not crash run() nor
        silently diverge from serve(): both default to job-fair."""
        exp = Experiment(scheduler="themis", n_workers=2)
        exp.add_job(user=0, procs=4, req_mb=10, end_s=0.5)
        assert exp.engine_config().policy.name == "job-fair"
        res = exp.run(0.5)
        assert res.completed[0] > 0 and res.policy == "job-fair"
        assert exp.serve().cluster.policy.name == "job-fair"

    def test_missing_legacy_key_is_keyerror(self):
        res = two_job_exp("fifo").run(1.0)
        with pytest.raises(KeyError):
            res["seeds"]      # batch-only key on a single-run result

    def test_facade_matches_raw_engine_entry_point(self):
        """The facade is sugar, not a fork: Experiment.run reproduces the
        low-level make_workload + run path bit-identically."""
        exp = two_job_exp("themis")
        res = exp.run(1.0)
        cfg, wl, table = exp.build()
        raw = run(cfg, wl, table, 1.0)
        np.testing.assert_array_equal(res.gbps, raw["gbps"])
        np.testing.assert_array_equal(res.completed, raw["completed"])


class TestRunResult:
    @pytest.fixture(scope="class")
    def res(self):
        return two_job_exp("themis").run(2.0)

    def test_structured_fields(self, res):
        assert isinstance(res, RunResult)
        assert res.scheduler == "themis" and res.policy == "job-fair"
        assert res.n_jobs == 2 and res.dropped == 0
        assert res.idle_worker_ticks >= 0
        assert res.gbps.shape[0] >= 2

    def test_legacy_dict_access_for_metrics_helpers(self, res):
        from repro.core import metrics
        assert res["bin_s"] == res.bin_s
        np.testing.assert_array_equal(res["gbps"], res.gbps)
        assert metrics.median_gbps(res, 0, 0.5, 1.5) > 0
        with pytest.raises(KeyError):
            res["nope"]

    def test_mean_and_cov(self, res):
        m = res.mean_gbps(t0=0.5, t1=1.5)
        assert m == pytest.approx(22.0, rel=0.1)   # ~server_bw saturated
        assert res.cov_gbps(0, 0.5, 1.5) >= 0.0

    def test_jain_fairness_symmetric_jobs_near_one(self, res):
        assert res.jain_fairness(0.5, 1.5) == pytest.approx(1.0, abs=0.02)

    def test_slowdown_vs_solo(self, res):
        solo = two_job_exp("themis").solo(0, 2.0)
        sd = res.slowdown(solo, job=0, t0=0.5, t1=1.5)
        assert sd == pytest.approx(2.0, rel=0.25)  # two equal jobs share 2:1

    def test_slowdown_for_non_first_job(self, res):
        """solo() re-declares the job at slot 0; slowdown(job=1) must read
        that slot, not the solo run's empty slot 1."""
        solo = two_job_exp("themis").solo(1, 2.0)
        sd = res.slowdown(solo, job=1, t0=0.5, t1=1.5)
        assert sd == pytest.approx(2.0, rel=0.25)

    def test_counters_block_is_json_ready(self, res):
        import json
        c = res.counters()
        assert set(c) == {"scheduler", "policy", "params_hash", "dropped",
                          "idle_worker_ticks", "tick_impl", "ticks", "lanes",
                          "kernel_invocations", "kernel_grid_steps",
                          "policy_levels", "chain_elements",
                          "jit_traces",
                          "compile_cache_requests", "compile_cache_hits"}
        assert c["tick_impl"] in ("ref", "pallas")
        # job-fair over the facade's 8 slots on one server: T^0 is [1, 8].
        assert (c["policy_levels"], c["chain_elements"]) == (1, 8)
        json.dumps(c)


class TestRunBatch:
    def test_lanes_bit_identical_to_sequential_runs(self):
        exp = two_job_exp("themis")
        batch = exp.run_batch(1.0, seeds=[0, 3])
        assert isinstance(batch, BatchRunResult) and batch.n_seeds == 2
        for k, s in enumerate([0, 3]):
            seq = dataclasses.replace(exp.engine_config(), seed=s)
            wl, table = make_workload(seq, exp.jobs)
            raw = run(seq, wl, table, 1.0)
            lane = batch.seed_result(k)
            np.testing.assert_array_equal(lane.gbps, raw["gbps"])
            assert lane.idle_worker_ticks == raw["idle_worker_ticks"]

    def test_mean_cov_reduction(self):
        batch = two_job_exp("themis").run_batch(1.0, seeds=[0, 1])
        m, cov = batch.mean_cov(lambda r: r.mean_gbps())
        assert m > 0 and cov >= 0

    def test_per_run_metrics_refuse_on_batch(self):
        """The inherited metrics would index the seed axis as the job axis;
        they must refuse, pointing at seed_result()/mean_cov()."""
        batch = two_job_exp("themis").run_batch(1.0, seeds=[0, 1])
        for call in (lambda: batch.mean_gbps(0), lambda: batch.job_gbps(0),
                     lambda: batch.cov_gbps(0), lambda: batch.jain_fairness(),
                     lambda: batch.slowdown(batch.seed_result(0))):
            with pytest.raises(TypeError, match="seed_result"):
                call()
        assert batch.seed_result(0).mean_gbps(0) > 0   # per-lane path works


class TestEverySchedulerViaFacade:
    """PR-3 acceptance: every registered scheduler runs via Experiment on
    BOTH planes, and the two planes compute identical share tables."""

    @pytest.mark.parametrize("sched", SCHEDULERS)
    def test_engine_plane(self, sched):
        res = two_job_exp(sched).run(2.0)
        assert res.completed[0] > 0 and res.completed[1] > 0
        assert res.dropped == 0
        assert np.isfinite(res.gbps).all()

    @pytest.mark.parametrize("sched", SCHEDULERS)
    def test_functional_plane_and_identical_share_tables(self, sched):
        exp = two_job_exp(sched)
        svc = exp.serve(autodrain=False)
        # one client per declared job, metadata carried over
        assert [c.job.user for c in svc.clients] == [0, 1]
        a, b = svc.client(0), svc.client(1)
        a.open("/a", "w")
        b.open("/b", "w")
        svc.drain()
        for i in range(20):
            a._req("write", "/a", offset=i * 8, data=b"x" * 8)
            b._req("write", "/b", offset=i * 8, data=b"y" * 8)
        done = svc.drain()
        assert len(done) == 40                     # everything drained
        # identical share tables: same scheduler object, and the engine-plane
        # config and the service's config resolve to the same params, so
        # tick_shares agrees elementwise on any snapshot.
        sobj = get_scheduler(sched)
        engine_cfg = exp.engine_config()
        assert sobj.params(engine_cfg) == sobj.params(svc.cluster.cfg)
        _, _, table = exp.build()
        j = engine_cfg.max_jobs
        view = TickView(
            qcount=jnp.asarray([[3, 1] + [0] * (j - 2)], jnp.int32),
            known=jnp.asarray([[True, True] + [False] * (j - 2)]),
            seg=jnp.zeros((1, j), jnp.float32),
            synced=jnp.zeros((j,), bool),
            live=jnp.ones((j,), bool))
        np.testing.assert_array_equal(
            np.asarray(sobj.tick_shares(engine_cfg, table, view)),
            np.asarray(sobj.tick_shares(svc.cluster.cfg, table, view)))

    @pytest.mark.parametrize("sched", SCHEDULERS)
    def test_config_carries_no_scheduler_fields(self, sched):
        """The flat per-scheduler knobs are gone for good: the facade's
        config exposes scheduler state only through ``scheduler`` +
        ``scheduler_params``."""
        cfg = two_job_exp(sched).engine_config()
        assert isinstance(cfg, EngineConfig)
        assert not {k for k in EngineConfig.__dataclass_fields__
                    if k.startswith(("gift_", "tbf_", "adaptbf_", "plan_"))}
