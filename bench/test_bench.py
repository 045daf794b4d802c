"""CPU checks of the benchmark's definition and yardsticks (no chip)."""
from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys

import pytest

from bench import peaks, spec, workcount

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == TOP_KEYS
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_every_entry_is_found_by_name(bench):
    for w in bench["workloads"]:
        cell = spec.resolve_cell(w["name"], bench)
        assert cell.config["lanes"] >= 1
        assert spec.make_jobs(cell.config)
        assert cell.traffic["scheduler"]
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/configs/")
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]))


def test_names_and_units_use_allowed_characters(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
            for key in ("config", "traffic"):
                if key in entry:
                    assert NAME.match(entry[key])
    assert len(names) == len(set(names))
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for w in bench["workloads"]:
        cell = spec.resolve_cell(w["name"], bench)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                         "sim_s_per_s"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in {x["name"] for x in cell.end_to_end}


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "fig12.themis",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("s,j,w,nbytes,flops", [
    (1, 8, 8, 576, 768.0),                 # themisio-fig12, one lane
    (128, 8, 8, 73728, 98304.0),           # themisio-fig7-128
])
def test_work_count_equals_tick_step_roofline(s, j, w, nbytes, flops):
    work = workcount.tick_step_work(s, j, w)
    assert work == {"bytes": nbytes, "flops": flops}
    from repro.roofline.analysis import tick_step_roofline

    orig = tick_step_roofline(s, j, w, device_kind="TPU v5 lite")
    assert (orig["bytes"], orig["flops"]) == (nbytes, flops)
    bound, which = workcount.roofline_s(work, "TPU v5 lite")
    assert which == orig["bound"] == "memory"
    assert bound * 1e6 == pytest.approx(orig["budget_us"])


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.device_peaks("TPU v99")
    with pytest.raises(ValueError):
        workcount.roofline_s({"bytes": 1, "flops": 1}, "cpu")
    assert peaks.device_peaks("TPU v5 lite")["hbm_bw"] == 819e9


def test_populations_are_the_copied_generators(bench):
    fig12 = spec.make_jobs(spec.resolve_cell("fig12.themis", bench).config)
    assert fig12 == [
        dict(user=0, size=1, procs=56, req_mb=10, start_s=0.0, end_s=60.0),
        dict(user=1, size=1, procs=56, req_mb=10, start_s=15.0, end_s=45.0)]
    fig7 = spec.make_jobs(spec.resolve_cell("fleet128.themis", bench).config)
    assert fig7 == [dict(user=0, size=128, procs=1024, req_mb=1, end_s=6.0)]


def test_call_seeds_come_from_the_seed_and_the_call():
    big = 2**31 + 977
    assert spec.call_seeds(big, 1, 8) == spec.call_seeds(big, 1, 8)
    assert spec.call_seeds(big, 1, 8) != spec.call_seeds(big, 2, 8)
    assert spec.call_seeds(big, 1, 8) != spec.call_seeds(big + 1, 1, 8)
    assert all(0 <= s < 2**32 for s in spec.call_seeds(-7, 0, 4))


def test_benchmark_file_is_small_and_one_line_fields(bench):
    assert os.path.getsize(spec.BENCHMARK_FILE) < 64 * 1024
    for group in ("configs", "workloads"):
        for entry in bench[group]:
            assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for m in bench["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
    json.dumps(bench)


def test_every_configuration_names_a_reference_under_bench(bench):
    for w in bench["workloads"]:
        cell = spec.resolve_cell(w["name"], bench)
        assert cell.reference.is_relative_to(spec.BENCH_DIR)
        assert cell.reference.is_file()
        ref = spec.load_reference(cell)
        assert callable(ref.make_simulator) and callable(ref.run_lanes)
    # The two published configurations name none: bench/reference.py.
    for name in ("fig12.themis", "fleet128.tbf"):
        assert spec.resolve_cell(name, bench).reference == \
            spec.DEFAULT_REFERENCE


#: The engine options each configuration gave ``Experiment`` when the
#: harness named them one by one; the options from the file are the same.
NAMED_OPTIONS = ("policy", "n_servers", "n_workers", "server_bw", "max_jobs",
                 "dt", "wheel", "ring_cap", "bin_ticks", "sync_ticks",
                 "sinkhorn_iters", "fabric_exponent")


@pytest.mark.parametrize("name", ["fig12.themis", "fleet128.themis",
                                  "fig12.tbf", "fleet128.tbf"])
def test_engine_options_of_the_existing_cells_are_unchanged(bench, name):
    from bench.run import build_experiment, engine_options
    from repro.api import Experiment
    from repro.core.scheduler import get_scheduler

    cell = spec.resolve_cell(name, bench)
    c, tr = cell.config, cell.traffic
    assert engine_options(c) == {k: c[k] for k in NAMED_OPTIONS}
    params = get_scheduler(tr["scheduler"]).params_cls(**tr.get("params", {}))
    named = Experiment(scheduler=tr["scheduler"], params=params,
                       **{k: c[k] for k in NAMED_OPTIONS})
    exp = build_experiment(cell)
    assert exp.engine_kw == named.engine_kw
    assert exp.engine_config() == named.engine_config()
    assert exp.jobs == named.add_jobs(spec.make_jobs(c)).jobs


def test_engine_options_reach_the_experiment_and_harness_keys_do_not(bench):
    from bench.run import build_experiment

    cell = spec.resolve_cell("fleet128.themis", bench)
    config = dict(cell.config, shard_servers=1, mesh_shape=[1, 1],
                  reference="bench/reference.py")
    exp = build_experiment(dataclasses.replace(cell, config=config))
    assert exp.engine_kw["shard_servers"] == 1
    assert exp.engine_kw["mesh_shape"] == (1, 1)
    assert exp.engine_config().mesh_shape == (1, 1)
    harness = {"entry", "lanes", "sim_seconds", "jobs", "reference",
               "source", "deployment", "guarantees", "assumed", "reduced",
               "precision"}
    assert not harness & set(exp.engine_kw)


@pytest.mark.parametrize("key,value", [("tick_impl", "ref"), ("seed", 3),
                                       ("scheduler", "fifo")])
def test_a_configuration_may_not_set_what_the_harness_owns(bench, key,
                                                          value):
    from bench.run import engine_options

    config = dict(spec.resolve_cell("fig12.themis", bench).config,
                  **{key: value})
    with pytest.raises(ValueError, match=key):
        engine_options(config)
