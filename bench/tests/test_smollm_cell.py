"""The SmolLM training cell's files, on the CPU at a tiny size.

    python3 -m pytest -q bench/tests/test_smollm_cell.py

* a tiny SmolLM cell with one-round chunks and no bank runs ``correct``
  from files alone, its round 1 compared as ``update1``;
* each planted training fault, and the float8 control, fail its limits;
* the work counts at the published widths, against the program's own
  parameter count.
"""
from __future__ import annotations

import json
import time

import pytest

from bench import common, faults, run

# the published shape at a tiny width and depth; float32 products, so that
# the program's gap to the reference is rounding alone
TINY_SMOLLM = {"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
               "vocab_size": 256, "dtype": "float32", "params": None}
TINY_TRAIN = {"nodes": 3, "pool": 8, "batch": 2, "seq_len": 32,
              "local_steps": 2}
# float32 on the CPU agrees with the reference to a few parts in 1e7; the
# gap of a fault or of float8 products is 1e-3 or more
LIMITS = {"loss": 1e-5, "consensus": 1e-5, "update1": 1e-4}
SEED = 2**32 + 29


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A checkout holding one tiny SmolLM training cell, made of files only,
    with the new cell's metric readers copied in."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench / sub).mkdir(parents=True)
    peaks = common.load_json(common.BENCH / "peaks.json")
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    cfg = dict(common.find("configs", "smollm-135m"), **TINY_SMOLLM)
    (bench / "configs" / "tiny-smollm.json").write_text(json.dumps(cfg))
    tr = dict(common.find("traffic", "train-k4-ring"), **TINY_TRAIN)
    (bench / "traffic" / "tiny-lm.json").write_text(json.dumps(tr))
    (bench / "limits" / "tiny-smollm.tiny-lm.json").write_text(
        json.dumps(LIMITS))
    spec = {
        "workloads": [{"name": "tiny-smollm.tiny-lm", "config": "tiny-smollm",
                       "traffic": "tiny-lm", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "rounds_per_s", "unit": "rounds/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(common, "ROOT", tmp_path)
    monkeypatch.setattr(common, "BENCH", bench)
    monkeypatch.setattr(common, "TRACE_DIR", tmp_path / ".bench_trace")
    return tmp_path


def _run(seed=SEED):
    cell = run.load_cell("tiny-smollm.tiny-lm")
    return run.run_cell(cell, seed, 0.5, False, t_start=time.time())


def test_smollm_cell_runs_from_files_alone(tree):
    res = _run()
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["compared"]) == set(LIMITS)
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}


@pytest.mark.parametrize("fault", faults.TRAIN_FAULTS)
def test_smollm_faults_are_caught(tree, fault):
    with faults.plant(fault):
        res = _run()
    assert not res["correct"], res["compared"]


def test_smollm_control_fails_the_limits(tree):
    from bench import check, control
    cell = run.load_cell("tiny-smollm.tiny-lm")
    numbers = control.train_control(
        cell, SEED, control.control_dtype(cell["config_data"]))
    assert not check.judge(numbers, LIMITS)[0], numbers


def test_smollm_work_at_the_published_width():
    import jax

    from bench.train import program_model_config
    from bench.work import smollm
    from repro.models import get_model
    cfg = common.find("configs", "smollm-135m")
    assert smollm.params(cfg) == 134_515_008 == cfg["params"]
    assert sum(smollm.leaf_sizes(cfg)) == cfg["params"]
    shapes = jax.eval_shape(get_model(program_model_config(cfg)).init,
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg["params"]
    tr = common.find("traffic", "train-k4-ring")
    assert smollm.tokens_per_round(tr) == 4 * 4 * 4 * 2048
    # 2 p and causal attention a token, three passes
    attn = 30 * 4 * 9 * 64 * 2049 / 2
    assert smollm.train_flops_per_round(cfg, tr) == \
        3 * (2 * 134_515_008 + attn) * 131072
    assert smollm.xent_flops_per_round(cfg, tr) == 6 * 576 * 49152 * 131072


def test_smollm_reference_params_match_the_program_layout():
    import jax

    from bench.reference import smollm as ref
    from bench.train import program_model_config
    from repro.models import get_model
    cfg = common.find("configs", "smollm-135m")
    mine = jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0), cfg))
    prog = jax.eval_shape(get_model(program_model_config(cfg)).init,
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, mine) == \
        jax.tree.map(lambda a: a.shape, prog)


def test_zipf_pool_is_seeded_and_skewed():
    import numpy as np

    from bench.reference import smollm as ref
    cfg = {"vocab_size": 1000}
    tr = {"nodes": 2, "pool": 4, "seq_len": 512, "zipf": 1.1}
    a = np.asarray(ref.make_pool(cfg, tr, SEED)["tokens"])
    b = np.asarray(ref.make_pool(cfg, tr, SEED)["tokens"])
    assert a.shape == (2, 4, 512) and a.dtype == np.int32
    assert (a == b).all() and a.min() >= 0 and a.max() < 1000
    # rank 1 takes about 1 / H(1000, 1.1) of the draws, some 15%
    assert 0.10 < float(np.mean(a == 0)) < 0.20
