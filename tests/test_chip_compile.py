"""The encode-path Pallas kernels compile for a TPU v5e (``interpret=False``),
and so do the serving cell's BMA predict program and the SmolLM training
cell's chunk program.

Each kernel case lowers one kernel at a real leaf width and compiles it for
one chip of a described ``v5e:2x2`` topology: the TPU compiler runs here
with no chip attached and refuses what Mosaic cannot lower, which interpret
mode never shows. Nothing executes, so these say nothing about results or
times.

The topology is described inside a module fixture — never at import, in a
``skipif`` or in ``parametrize`` — so every test worker collects the same
cases and only the worker that runs this file loads the TPU library. The
persistent compilation cache is off around the compiles: an entry written
for a described chip cannot be read back without one.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.config import get_arch
from repro.core.posterior import BankPredictor
from repro.kernels.block_topk import ROWS_PER_TILE
from repro.kernels.fused_compress import delta_pack_pallas, grid_quant_pallas
from repro.kernels.pack import pack_topk_pallas, unpack_topk_pallas
from repro.models import get_model

BLOCK, RATIO, LEVELS = 1024, 0.01, 16
K = 11                                   # ceil(RATIO * BLOCK)
LEAVES = {
    "lenet_conv1": 5 * 5 * 1 * 6,        # lenet-radar conv1 kernel: one
    #                                      padded tile, as the chunk runs it
    "lenet_fc1": 11712 * 220,            # lenet-radar fc1 kernel, 2,576,640
    "smollm_embed": 49152 * 576,         # smollm-135m embed_tokens
}


def _round_up(n, m):
    return -(-n // m) * m


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _cases(n):
    """kernel name -> (fn, argument shapes/dtypes) for an n-element leaf."""
    nb = _round_up(-(-n // BLOCK), ROWS_PER_TILE)
    blocks, carrier, f32 = (nb, BLOCK), (nb, K), jnp.float32
    return {
        "pack": (lambda x: pack_topk_pallas(x, K, interpret=False),
                 [(blocks, f32)]),
        "unpack": (lambda v, i: unpack_topk_pallas(v, i, BLOCK,
                                                   interpret=False),
                   [(carrier, f32), (carrier, jnp.int32)]),
        "delta_pack": (lambda t, v: delta_pack_pallas(t, v, K,
                                                      interpret=False),
                       [(blocks, f32), (blocks, f32)]),
        "grid_quant": (lambda x, u, s: grid_quant_pallas(
            x, u, s, LEVELS, jnp.int8, interpret=False),
            [(carrier, f32), (carrier, f32), ((1, 1), f32)]),
    }


@pytest.mark.parametrize("leaf", sorted(LEAVES))
@pytest.mark.parametrize("kernel", ["pack", "unpack", "delta_pack",
                                    "grid_quant"])
def test_kernel_compiles_for_v5e(one_chip, kernel, leaf):
    fn, arg_specs = _cases(LEAVES[leaf])[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bma_predict_compiles_packed_for_v5e(one_chip):
    """The predict program of the serving cell (a 40 x 10 bank at full
    LeNet width, 8 slots of 256 x 63 maps) compiles for the chip with the
    conv tower packed (the ``bma_packed`` scope), and fc1 reads the
    float32 bank in place: the bfloat16 conversion of the bank stays inside
    fc1's fusion, no op of the program's entry writes a copy of it."""
    cfg = get_arch("lenet-radar").config
    model = get_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    bank = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (40, 10) + a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((8,) + tuple(cfg.input_hw) + (1,), jnp.float32,
                             sharding=one_chip)
    predictor = BankPredictor(lambda p, b: model.logits(p, b), node_axis=1)
    text = predictor._fn.lower(bank, {"x": x}).compile().as_text()
    assert "bma_packed" in text and predictor.packed_traces == 1
    fc1 = 400 * params["fc1"]["w"].size
    entry = re.search(r"^ENTRY .*?^}", text, re.M | re.S).group(0)
    bf16_sizes = [math.prod(int(n) for n in d.split(",") if n)
                  for d in re.findall(r"bf16\[([0-9,]*)\]", entry)]
    assert fc1 not in bf16_sizes


def test_smollm_train_chunk_fits_one_v5e(one_chip, monkeypatch):
    """The chunk program of the ``smollm-135m.train-k4-ring`` cell (K=4
    nodes of the whole 30-layer model, 4 x 2,048-token sequences a local
    step, float32 params, v and v-bar) compiles for one chip through the
    chunked, recomputed loss and the fused attention kernel, and its
    arguments and temporaries fit a 16 GB chip with room for the process's
    other buffers.

    The host here is a CPU, so the platform checks of the dispatch and of
    the kernel are patched to take the TPU's path. The kernel's forward and
    backward are custom calls under the ``attention`` scope, and no
    float32 block of 512 queries' scores against all 2,048 keys is left."""
    from bench import common, lm_scopes, scopes, train
    from bench.reference import smollm as ref
    from repro.config import FedConfig, TopologyConfig
    from repro.core import (build_topology, init_fed_state, make_compressor,
                            make_round_fn)
    from repro.data.partition import DeviceShards
    from repro.kernels import flash_attention
    from repro.models import attention
    from repro.train.engine import EngineCarry, make_engine
    monkeypatch.setattr(attention, "interpret_mode", lambda: False)
    monkeypatch.setattr(flash_attention, "interpret_mode", lambda: False)
    flash_before = attention.flash_attention_traces

    cfg = common.find("configs", "smollm-135m")
    tr = common.find("traffic", "train-k4-ring")
    model = get_model(train.program_model_config(cfg))
    topo = TopologyConfig(graph=tr["graph"])
    fed = FedConfig(num_nodes=tr["nodes"], local_steps=tr["local_steps"],
                    eta=tr["eta"], zeta=tr["zeta"], topology=tr["graph"],
                    topology_cfg=topo, compressor=tr["codec"],
                    compress_ratio=tr["ratio"], block_size=tr["block"],
                    fused_compress=tr["fused"], algorithm="cdbfl")
    round_fn = make_round_fn("cdbfl", model.loss, fed,
                             build_topology(topo, fed.num_nodes).omega,
                             make_compressor(fed))
    params = jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda p: init_fed_state(
        p, fed, key=jax.random.PRNGKey(1)), params)
    data = {"tokens": jax.ShapeDtypeStruct(
        (tr["nodes"], tr["pool"], tr["seq_len"]), jnp.int32)}
    sizes = jax.ShapeDtypeStruct((tr["nodes"],), jnp.int32)
    engine = make_engine("scan", round_fn, DeviceShards(
        data=data, sizes=sizes, example_field="tokens"), fed.local_steps,
        tr["batch"], chunk=tr["chunk"])
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    compiled = engine._chunk_fn(tr["chunk"]).lower(
        on_chip((data, sizes)), on_chip(EngineCarry(state, key, None)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert model.chunked_xent_traces == 1
    assert attention.flash_attention_traces > flash_before
    text = compiled.as_text()
    assert "xent" in text and "attention" in text
    # the kernels as the benchmark's scope reader sees them: every one
    # under ``attention``, all of its time given to ``attention_ms``
    ops = scopes.parse_hlo(text)
    shares = scopes.op_shares(text, scopes.ROUND_SCOPES + lm_scopes.LM_SCOPES)
    kernels = [name for name, op in ops.items() if op.opcode == "custom-call"
               and name.startswith("flash_attention_")]
    assert {name.split(".")[0] for name in kernels} == {
        "flash_attention_fwd", "flash_attention_bwd"}, kernels
    for name in kernels:
        assert scopes.scope_of(ops[name].op_name, ("attention",)), name
        assert shares[name] == {"attention": 1.0}, (name, shares[name])
    assert not re.search(r"f32\[[0-9,]*512,2048\]", text)
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 14.758 GB with the fused attention kernel, 14.758 with chunked_gqa:
    # the peak is not in attention; without the chunked loss and
    # recomputation the float32 logits of 16 x 2,048 tokens alone take
    # 6.4 GB
    assert peak < 15.5e9, peak
