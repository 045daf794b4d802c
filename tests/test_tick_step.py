"""Fused tick-step kernel vs the legacy scan — bit-identity on both planes.

The contract: ``EngineConfig.tick_impl`` changes *where* the worker phase
runs, never what it computes.  For every registered scheduler the fused
engine must reproduce the legacy scan's final state bit-for-bit — shares,
per-job bytes, completed counts, queue state, and the PRNG key trajectory
(stream identity) — and schedulers without kernel support must fall back
to the scan transparently.  The op-level tests hold the Pallas kernel
(interpret mode on CPU) to the jnp oracle under the same standard.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_shim import given, settings, st

from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import engine
from repro.core.engine import (EngineConfig, make_workload, resolve_tick_impl,
                               run, run_batch)
from repro.core.policy import Policy
from repro.core.scheduler import available_schedulers, get_scheduler
from repro.bb.service import BBClient, BBCluster, JobMeta
from repro.kernels.tick_step.kernel import (MAX_BLOCK_ROWS,
                                            VMEM_BLOCK_BUDGET,
                                            tick_step_grid, tick_step_pallas)
from repro.kernels.tick_step.ops import tick_step
from repro.kernels.tick_step.ref import MODES, tick_step_ref

LOWERED = ("themis", "fifo")


def _rand_inputs(seed, s, j, w, lead=()):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shares = jax.random.uniform(ks[0], lead + (s, j))
    qcount = jax.random.randint(ks[1], lead + (s, j), 0, 4)
    # ring stamps grow along the window axis like a real arrival ring
    window = jnp.cumsum(jax.random.uniform(ks[2], lead + (s, j, w)), axis=-1)
    free = jax.random.uniform(ks[3], lead + (s, w)) < 0.8
    u = jax.random.uniform(ks[4], lead + (s, w))
    return shares, qcount, window, free, u


OUTPUTS = ("sel", "valid", "demand_any", "qcount", "pops")


class TestTickStepOp:
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("s,j,w", [(1, 4, 2), (2, 16, 8), (4, 130, 8),
                                       (8, 256, 4)])
    def test_pallas_matches_ref(self, mode, s, j, w):
        args = _rand_inputs(s * 1000 + j + w, s, j, w)
        ref = tick_step_ref(*args, mode=mode)
        pal = tick_step(*args, mode=mode, impl="pallas")
        for name, a, b in zip(("sel", "valid", "demand_any", "qcount",
                               "pops"), ref, pal):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{mode}/{name}")

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 5), st.integers(2, 40), st.integers(1, 8),
           st.integers(0, 10_000))
    def test_property_pallas_matches_ref(self, s, j, w, seed):
        args = _rand_inputs(seed, s, j, w)
        for mode in MODES:
            ref = tick_step_ref(*args, mode=mode)
            pal = tick_step(*args, mode=mode, impl="pallas")
            for a, b in zip(ref, pal):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_pops_bounded_by_queue_and_workers(self):
        shares, qcount, window, free, u = _rand_inputs(1, 3, 12, 6)
        _, valid, _, qout, pops = tick_step(shares, qcount, window, free, u,
                                            mode="themis", impl="ref")
        assert (np.asarray(qout) >= 0).all()
        assert (np.asarray(qout) + np.asarray(pops)
                == np.asarray(qcount)).all()
        assert np.asarray(pops).sum(axis=-1).max() <= 6

    def test_unknown_mode_and_impl_fail_loudly(self):
        args = _rand_inputs(0, 1, 4, 2)
        with pytest.raises(ValueError, match="mode"):
            tick_step(*args, mode="lifo")
        with pytest.raises(ValueError, match="impl"):
            tick_step(*args, impl="cuda")

    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    def test_themis_takes_no_window(self, impl):
        shares, qcount, window, free, u = _rand_inputs(5, 3, 12, 8)
        with_window = tick_step(shares, qcount, window, free, u,
                                mode="themis", impl=impl)
        without = tick_step(shares, qcount, None, free, u, mode="themis",
                            impl=impl)
        for name, a, b in zip(OUTPUTS, with_window, without):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        with pytest.raises(ValueError, match="window"):
            tick_step(shares, qcount, None, free, u, mode="fifo", impl=impl)


def _lane_loop(args, axes, lanes, mode):
    """The per-lane calls a vmap over ``axes`` stands for."""
    outs = [tick_step_pallas(*[a if ax is None else a[b]
                               for a, ax in zip(args, axes)], mode=mode)
            for b in range(lanes)]
    return [np.stack([np.asarray(o[k]) for o in outs])
            for k in range(len(OUTPUTS))]


class TestVmapFold:
    """vmap lanes fold into the kernel's rows, bit for bit."""

    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("s", [1, 5, 128])
    def test_vmap_equals_lane_loop(self, mode, s):
        lanes = 3
        args = list(_rand_inputs(s + 7, s, 8, 4, lead=(lanes,)))
        args[0] = args[0][0]                    # shares shared by every lane
        axes = (None, 0, 0, 0, 0)
        f = functools.partial(tick_step_pallas, mode=mode)
        got = jax.vmap(f, in_axes=axes)(*args)
        for name, a, b in zip(OUTPUTS, got, _lane_loop(args, axes, lanes,
                                                       mode)):
            np.testing.assert_array_equal(np.asarray(a), b,
                                          err_msg=f"{mode}/{s}/{name}")

    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("s", [1, 5, 128])
    def test_nested_vmap_equals_lane_loop(self, mode, s):
        outer, inner = 2, 3
        args = list(_rand_inputs(s + 11, s, 8, 2, lead=(outer, inner)))
        args[0] = args[0][:, 0]                 # shares vary by outer lane only
        f = functools.partial(tick_step_pallas, mode=mode)
        got = jax.vmap(jax.vmap(f, in_axes=(None, 0, 0, 0, 0)))(*args)
        for o in range(outer):
            want = _lane_loop([args[0][o]] + [a[o] for a in args[1:]],
                              (None, 0, 0, 0, 0), inner, mode)
            for name, a, b in zip(OUTPUTS, got, want):
                np.testing.assert_array_equal(np.asarray(a[o]), b,
                                              err_msg=f"{mode}/{s}/{name}")


class TestResolveTickImpl:
    def test_lowered_schedulers_honor_pallas(self):
        for name in LOWERED:
            cfg = EngineConfig(scheduler=name, tick_impl="pallas")
            assert resolve_tick_impl(cfg, get_scheduler(name)) == "pallas"

    def test_non_lowered_schedulers_fall_back(self):
        for name in available_schedulers():
            if name in LOWERED:
                continue
            cfg = EngineConfig(scheduler=name, tick_impl="pallas")
            assert resolve_tick_impl(cfg, get_scheduler(name)) == "ref"

    def test_ref_always_wins(self):
        for name in available_schedulers():
            cfg = EngineConfig(scheduler=name, tick_impl="ref")
            assert resolve_tick_impl(cfg, get_scheduler(name)) == "ref"

    def test_auto_off_tpu_is_ref(self):
        cfg = EngineConfig(scheduler="themis", tick_impl="auto")
        expect = "pallas" if jax.default_backend() == "tpu" else "ref"
        assert resolve_tick_impl(cfg, get_scheduler("themis")) == expect

    def test_unknown_impl_fails_loudly(self):
        cfg = EngineConfig(scheduler="themis", tick_impl="fused")
        with pytest.raises(ValueError, match="tick_impl"):
            resolve_tick_impl(cfg, get_scheduler("themis"))


def _jobs():
    return [
        dict(user=0, size=2, procs=40, req_mb=8, think_s=0.002),
        dict(user=1, size=1, procs=20, req_mb=4,
             phases=[dict(start_s=0.0, duration_s=0.1, arrival="poisson",
                          rate_hz=300),
                     dict(start_s=0.15, duration_s=0.2)]),
        dict(user=2, size=1, procs=10, req_mb=16, start_s=0.05,
             think_s=0.001),
    ]


def _final_states(scheduler, seconds=0.3, seed=3):
    cfg_ref = EngineConfig(n_servers=2, max_jobs=8, n_workers=4,
                           scheduler=scheduler,
                           policy=Policy.parse("user-fair"),
                           tick_impl="ref", seed=seed)
    cfg_pal = dataclasses.replace(cfg_ref, tick_impl="pallas")
    wl, table = make_workload(cfg_ref, _jobs())
    return (run(cfg_ref, wl, table, seconds)["state"],
            run(cfg_pal, wl, table, seconds)["state"])


def _assert_states_equal(sr, sp, tag):
    for name in sr._fields:
        a, b = getattr(sr, name), getattr(sp, name)
        if name == "aux":
            for f in a._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
                    err_msg=f"{tag}: aux.{f}")
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{tag}: {name}")


class TestEngineBitIdentity:
    """tick_impl='pallas' == tick_impl='ref', full final state, per scheduler.

    The comparison covers every EngineState leaf — bytes_bin (per-job bytes),
    completed, qcount/head/ring, free_at, aux, AND state.key: equal final
    keys prove the two paths consumed the PRNG stream identically."""

    @pytest.mark.parametrize("scheduler", available_schedulers())
    def test_full_state_bitwise_equal(self, scheduler):
        sr, sp = _final_states(scheduler)
        _assert_states_equal(sr, sp, scheduler)

    def test_fused_path_actually_ran_work(self):
        sr, _ = _final_states("themis")
        assert int(np.asarray(sr.completed).sum()) > 0


class TestEngineBatchBitIdentity:
    """run_batch lanes fold into the kernel inside the scan under vmap:
    tick_impl='pallas' == 'ref', full final state of every lane."""

    @pytest.mark.parametrize("scheduler", LOWERED)
    def test_run_batch_full_state_bitwise_equal(self, scheduler):
        cfg_ref = EngineConfig(n_servers=2, max_jobs=8, n_workers=4,
                               scheduler=scheduler,
                               policy=Policy.parse("user-fair"),
                               tick_impl="ref")
        wl, table = make_workload(cfg_ref, _jobs())
        out = {impl: run_batch(dataclasses.replace(cfg_ref, tick_impl=impl),
                               wl, table, 0.3, seeds=[3, 8])
               for impl in ("ref", "pallas")}
        assert out["pallas"]["tick_impl"] == "pallas"
        _assert_states_equal(out["ref"]["state"], out["pallas"]["state"],
                             scheduler)
        assert int(np.asarray(out["ref"]["completed"]).sum()) > 0


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


def _program_jaxpr(cfg, wl, table, seconds, seeds=None):
    if seeds is None:
        fn, args, _ = engine._run_program(cfg, wl, table, seconds)
    else:
        fn, args, _ = engine._batch_program(cfg, wl, table, seconds, seeds,
                                            None)
    return jax.make_jaxpr(fn)(*args).jaxpr


def _fused_cfg(scheduler, n_servers, **kw):
    return EngineConfig(n_servers=n_servers, max_jobs=8, n_workers=8,
                        scheduler=scheduler, policy=Policy.parse("user-fair"),
                        tick_impl="pallas", wheel=256, **kw)


class TestLaunchGeometry:
    """What the compiled program holds: the kernel's grid, and no ring
    window in themis mode."""

    def test_grid_rule(self):
        # Both themis cells' geometry: every row in one step.
        assert tick_step_grid(8, 8, 8, "themis") == (8, 1)
        assert tick_step_grid(128, 8, 8, "themis") == (128, 1)
        assert tick_step_grid(5, 8, 8, "themis") == (8, 1)
        # Past the row cap, the fewest steps of equal tiles.
        assert tick_step_grid(256, 8, 8, "themis") == (128, 2)
        assert tick_step_grid(200, 8, 8, "themis") == (104, 2)
        # Fifo's [rows, Jp, W] window: 8.6 MiB of blocks at 128 rows of
        # J=8 fit the budget; at J=1024 it is 0.5 MiB a row, so 16 rows.
        assert tick_step_grid(128, 8, 8, "fifo") == (128, 1)
        assert tick_step_grid(128, 1024, 4, "fifo") == (16, 8)
        assert tick_step_grid(128, 1024, 4, "themis") == (128, 1)

    @settings(max_examples=50, deadline=None)
    @given(st.integers(1, 2000), st.integers(1, 2048), st.integers(1, 16),
           st.sampled_from(MODES))
    def test_grid_rule_covers_rows_in_fewest_fitting_steps(self, rows, j, w,
                                                           mode):
        br, steps = tick_step_grid(rows, j, w, mode)
        jp = -(-j // 128) * 128
        words = 4 * jp + 5 * 128 + (jp * 128 if mode == "fifo" else 0)
        assert br % 8 == 0 and 8 <= br <= MAX_BLOCK_ROWS
        assert br * steps >= rows > br * (steps - 1) - 8
        assert br == 8 or 4 * br * words <= VMEM_BLOCK_BUDGET

    @pytest.mark.parametrize("scheduler,n_servers,lanes", [
        ("themis", 1, 8),      # Fig. 12: 8 seed lanes x one node
        ("themis", 128, 1),    # 128 nodes
        ("fifo", 128, 1),
        ("themis", 32, 8),     # 256 rows: two steps
    ])
    def test_grid_steps_counter_matches_program(self, scheduler, n_servers,
                                                lanes):
        cfg = _fused_cfg(scheduler, n_servers)
        jobs = [dict(user=0, size=1, procs=4 * n_servers, req_mb=1,
                     think_s=0.001),
                dict(user=1, size=1, procs=2 * n_servers, req_mb=2)]
        wl, table = make_workload(cfg, jobs)
        seeds = list(range(lanes)) if lanes > 1 else None
        jaxpr = _program_jaxpr(cfg, wl, table, 0.003, seeds)
        grids = [tuple(e.params["grid_mapping"].grid) for e in _eqns(jaxpr)
                 if e.primitive.name == "pallas_call"]
        steps = tick_step_grid(lanes * n_servers, 8, 8,
                               get_scheduler(scheduler).kernel_select_mode)[1]
        assert grids == [(steps,)]
        assert steps == (2 if lanes * n_servers > MAX_BLOCK_ROWS else 1)
        out = (run(cfg, wl, table, 0.003) if seeds is None
               else run_batch(cfg, wl, table, 0.003, seeds=seeds))
        assert out["counters"]["kernel_grid_steps"] == steps
        ref = run(dataclasses.replace(cfg, tick_impl="ref"), wl, table, 0.003)
        assert ref["counters"]["kernel_grid_steps"] == 0

    @pytest.mark.parametrize("scheduler", LOWERED)
    def test_only_fifo_gathers_a_ring_window(self, scheduler):
        cfg = _fused_cfg(scheduler, 2, ring_cap=48)
        wl, table = make_workload(cfg, _jobs())
        arr_time = (cfg.n_servers, cfg.max_jobs, cfg.ring_cap)
        window_gathers = [
            e for e in _eqns(_program_jaxpr(cfg, wl, table, 0.003))
            if e.primitive.name == "gather"
            and tuple(e.invars[0].aval.shape) == arr_time]
        assert len(window_gathers) == (scheduler == "fifo")


class TestServicePlane:
    """The bb plane's tick_impl seam: same drain order either way."""

    @pytest.mark.parametrize("scheduler", LOWERED)
    def test_drain_identical_across_impls(self, scheduler):
        def drained(impl):
            bb = BBCluster(n_servers=2, scheduler=scheduler,
                           policy="user-fair", seed=7, tick_impl=impl)
            clients = [BBClient(bb, JobMeta(job_id=i, user=i % 2,
                                            size=1 + i), autodrain=False)
                       for i in range(3)]
            for c in clients:
                c.open(f"/j{c.job.job_id}", "w")
            bb.drain()
            for i in range(8):
                for c in clients:
                    c._req("write", f"/j{c.job.job_id}", offset=i * 64,
                           data=b"x" * 64)
            done = bb.drain()
            return [(r.job.job_id, r.seqno, r.done_at) for r in done]

        assert drained("ref") == drained("pallas")
