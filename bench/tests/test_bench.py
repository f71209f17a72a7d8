"""The benchmark's own tests, on the CPU at small sizes.

    python3 -m pytest -q bench/tests

* the trace reduction on a synthetic and on a recorded trace;
* the work counts at the paper's sizes;
* a configuration, a traffic mix, limits and a per-layer metric found by
  name, with no edit of the harness;
* the run refuses a machine without a TPU and prints no result;
* the check: the program agrees with the reference (``correct`` true), and
  the control (the reference with float8 products) and every planted fault
  come out not correct.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from bench import common, faults, generate, run, trace

ROOT = common.ROOT


# -- trace reduction ---------------------------------------------------------

def _planes():
    ms = 1_000_000
    host = ("/host:CPU", [("python", [
        ("bench.window", 0, 100 * ms),
        ("dispatch_chunk", 0, 60 * ms),
        ("wait", 60 * ms, 100 * ms)])])
    dev0 = ("/device:TPU:0", [
        ("XLA Ops", [("while.3", 10 * ms, 40 * ms),     # wraps fusion.1
                     ("fusion.1", 10 * ms, 30 * ms),
                     ("fusion.1", 20 * ms, 40 * ms),     # overlaps
                     ("collective-permute-done", 50 * ms, 55 * ms),
                     ("fusion.2", 95 * ms, 120 * ms)]),  # clipped at 100
        ("XLA Modules", [("jit_chunk(1)", 10 * ms, 55 * ms)])])
    dev1 = ("/device:TPU:1", [
        ("XLA Ops", [("fusion.1", 0, 50 * ms)]),
        ("XLA Modules", [("jit_chunk(1)", 0, 50 * ms)])])
    return [host, dev1, dev0]


def test_trace_busy_union_idle_and_sums():
    r = trace.reduce_planes(_planes())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["devices"] == 2
    # dev0: [10,40] + [50,55] + [95,100] = 40 ms; dev1: 50 ms
    assert r["busy_by_device"] == pytest.approx([0.04, 0.05])
    assert r["busy_s"] == pytest.approx(0.045)
    # fusion.1: dev0 20+20 ms, dev1 50 ms -> mean 45 ms per device
    assert r["op_time"]["fusion.1"] == pytest.approx(0.045)
    assert r["op_time"]["fusion.2"] == pytest.approx(0.0025)
    assert "while.3" not in r["op_time"]
    assert r["op_time"]["collective-permute-done"] == pytest.approx(0.0025)
    assert r["module_count"]["jit_chunk(1)"] == pytest.approx(1.0)
    # dev0's gaps: [0,10] and [40,50] under dispatch_chunk, [55,95] in wait
    assert r["idle_gaps"][0] == ["wait", pytest.approx(0.04)]
    assert r["idle_s_by_span"]["dispatch_chunk"] == pytest.approx(0.02)


def test_short_op_names():
    assert trace.short_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion.12"
    assert trace.short_name("jit_chunk(123)") == "jit_chunk(123)"


def test_trace_needs_the_window_span():
    with pytest.raises(ValueError):
        trace.reduce_planes([("/host:CPU", [("t", [("x", 0, 5)])])])


def test_recorded_trace_is_read(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("dispatch_chunk"):
            jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    r = trace.reduce(str(path))
    assert r["window_s"] > 0
    assert r["devices"] == 0 and r["busy_s"] == 0.0     # no TPU plane here


# -- work counts -------------------------------------------------------------

def test_lenet_work_at_the_papers_size():
    from bench.work import codec, lenet
    cfg = common.find("configs", "lenet-radar")
    assert lenet.forward_flops_per_sample(cfg) == 24_292_320
    assert lenet.params(cfg) == 2_598_846 == cfg["params"]
    tr = common.find("traffic", "train-k10-ring")
    assert lenet.train_flops_per_round(cfg, tr) == 3 * 24_292_320 * 400
    sizes = lenet.leaf_sizes(cfg)
    # 11 kept of every 1024-entry block, 4 + 2 bytes each
    assert codec.wire_bytes([1024, 1], 0.01, 1024) == 2 * 11 * 6
    assert codec.ideal_bytes_per_node(sizes, 0.01, 1024) > 12 * sum(sizes)


def test_reference_params_match_the_program_layout():
    import jax
    from bench.reference import lenet as ref
    from bench.train import program_model_config
    from repro.models import get_model
    cfg = common.find("configs", "lenet-radar")
    mine = jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0), cfg))
    prog = jax.eval_shape(get_model(program_model_config(cfg)).init,
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, mine) == \
        jax.tree.map(lambda a: a.shape, prog)


def test_arrivals_fix_the_work_per_seed():
    a, fa = generate.poisson_arrivals(2**33 + 5, 100.0, 10.0, 64)
    b, fb = generate.poisson_arrivals(7, 100.0, 10.0, 64)
    assert len(a) == len(b) == 1000
    assert a[-1] < 10.0 and b[-1] < 10.0
    assert (a != b).any()
    a2, _ = generate.poisson_arrivals(2**33 + 5, 100.0, 10.0, 64)
    assert (a == a2).all()


# -- BENCHMARK.json -------------------------------------------------------------

def test_benchmark_json_names_only_files_that_exist():
    import re
    spec = common.load_json(ROOT / "BENCHMARK.json")
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert 1 <= spec["run_seconds"] <= 51
    cells = {w["name"] for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert name.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["name"] in {w["config"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        assert name.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (common.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (common.BENCH / "limits" / f"{w['name']}.json").is_file()
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for m in spec["end_to_end"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert (common.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        assert set(m.get("workloads", cells)) <= cells


# -- small cells, found by name ----------------------------------------------

TINY_LENET = {"input_hw": [32, 16], "fc1": 32, "params": None}
TINY_TRAIN = {"nodes": 4, "pool": 8, "batch": 2, "local_steps": 2,
              "bank_capacity": 8}
TINY_SERVE = {"samples": 4, "nodes": 3, "frames": 64, "rate": 50.0,
              "checked": 16}
TRAIN_LIMITS = {"loss": 1e-4, "consensus": 1e-4, "update1": 1e-3,
                "change3": 1e-3}
SERVE_LIMITS = {"probs": 1e-4, "entropy": 1e-4, "abstain": 0}


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A checkout holding one tiny training cell and one tiny serving cell,
    with a per-layer metric of their own, made of files only."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench / sub).mkdir(parents=True)
    # the CPU gets peaks here only, so that a traced run can be read
    peaks = common.load_json(common.BENCH / "peaks.json")
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    cfg = dict(common.find("configs", "lenet-radar"), **TINY_LENET)
    (bench / "configs" / "tiny-lenet.json").write_text(json.dumps(cfg))
    tr = dict(common.find("traffic", "train-k10-ring"), **TINY_TRAIN)
    (bench / "traffic" / "tiny-train.json").write_text(json.dumps(tr))
    sv = dict(common.find("traffic", "serve-bma-steady"), **TINY_SERVE)
    (bench / "traffic" / "tiny-serve.json").write_text(json.dumps(sv))
    (bench / "limits" / "tiny-lenet.tiny-train.json").write_text(
        json.dumps(TRAIN_LIMITS))
    (bench / "limits" / "tiny-lenet.tiny-serve.json").write_text(
        json.dumps(SERVE_LIMITS))
    (bench / "metrics" / "rounds_seen.train.py").write_text(
        "def read(ctx):\n    return float(ctx['counts']['rounds'])\n")
    spec = {
        "workloads": [
            {"name": "tiny-lenet.tiny-train", "config": "tiny-lenet",
             "traffic": "tiny-train", "chips": 1, "why": "test"},
            {"name": "tiny-lenet.tiny-serve", "config": "tiny-lenet",
             "traffic": "tiny-serve", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "rounds_per_s", "unit": "rounds/s",
             "workloads": ["tiny-lenet.tiny-train"]},
            {"name": "serve_p95_ms", "unit": "ms",
             "workloads": ["tiny-lenet.tiny-serve"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "rounds_seen.train", "unit": "rounds",
                       "workloads": ["tiny-lenet.tiny-train"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(common, "ROOT", tmp_path)
    monkeypatch.setattr(common, "BENCH", bench)
    monkeypatch.setattr(common, "TRACE_DIR", tmp_path / ".bench_trace")
    return tmp_path


def _run(name, seed=2**32 + 17, trace_on=False, seconds=1.0):
    cell = run.load_cell(name)
    return run.run_cell(cell, seed, seconds, trace_on, t_start=time.time())


def test_new_training_cell_runs_from_files_alone(tree):
    res = _run("tiny-lenet.tiny-train")
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    assert set(res["compared"]) == set(TRAIN_LIMITS)


def test_new_metric_reader_is_found(tree):
    res = _run("tiny-lenet.tiny-train", trace_on=True)
    assert res["correct"]
    assert res["metrics"]["rounds_seen.train"]["value"] == \
        res["counts"]["rounds"]
    assert "busy_s" in res["device"] and "breakdown" in res


def test_new_serving_cell_runs_from_files_alone(tree):
    res = _run("tiny-lenet.tiny-serve")
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] == 50
    assert set(res["metrics"]) == {"serve_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", faults.TRAIN_FAULTS)
def test_training_faults_are_caught(tree, fault):
    with faults.plant(fault):
        res = _run("tiny-lenet.tiny-train")
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("fault", faults.SERVE_FAULTS)
def test_serving_faults_are_caught(tree, fault):
    with faults.plant(fault):
        res = _run("tiny-lenet.tiny-serve")
    assert not res["correct"], res["compared"]


def test_control_fails_the_training_limits(tree):
    from bench import check, control
    cell = run.load_cell("tiny-lenet.tiny-train")
    numbers = control.train_control(
        cell, 2**32 + 17, control.control_dtype(cell["config_data"]))
    assert not check.judge(numbers, TRAIN_LIMITS)[0], numbers


def test_control_fails_the_serving_limits(tree):
    from bench import check, control
    cell = run.load_cell("tiny-lenet.tiny-serve")
    numbers = control.serve_control(
        cell, 2**32 + 17, control.control_dtype(cell["config_data"]))
    assert not check.judge(numbers, SERVE_LIMITS)[0], numbers


# -- no chip, no result --------------------------------------------------------

def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "lenet-radar.train-k10-ring", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
