"""Fused compress-in-update path (DESIGN.md §13).

Contracts pinned here:

* ``FusedCodec(fused=True).encode_pair(theta, v)`` is bitwise-identical —
  under a common jit context — to its ``fused=False`` two-pass oracle
  (same stages, same keys, residual materialized) for every eligible
  pipeline in the DSL, on f32 and bf16 control variates. The jit context
  matters: XLA folds division-by-constant into reciprocal-multiply under
  jit but not op-by-op, a last-ulp effect pinned in test_kernels.py.
* Ineligible pipelines (no Pallas block-top-k stage 0) and passthrough
  leaves fall back transparently to the two-pass encode.
* PerLayerPipeline routes leaves by tree-path pattern, records the
  per-leaf stages in the payload, and decodes self-describingly.
* Engine trajectories (host/scan/shard) are bitwise-unchanged by the
  ``fused`` flag.
* The HBM ledger certifies the tentpole: fused traffic is >=2x below
  two-pass and within 1.5x of the ``2p reads + wire writes`` bound.
* The int8 DeviceSampleBank stores quantized slots with per-row scales
  and keeps the f32 bank's ring/admit semantics.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FedConfig
from repro.core import (build_topology, init_fed_state, make_compressor,
                        make_round_fn, resolve_topology)
from repro.core.compression import (FusedCodec, PerLayerPipeline,
                                    encode_hbm_bytes, leaf_stages,
                                    parse_layer_rules, parse_pipeline)
from repro.core.posterior import DeviceSampleBank
from repro.data.partition import DeviceShards
from repro.train.engine import make_engine

KEY = jax.random.PRNGKey(0)
NDEV = len(jax.devices())
needs4 = pytest.mark.skipif(NDEV < 4, reason="needs >=4 devices "
                            "(XLA_FLAGS=--xla_force_host_platform_"
                            "device_count=8)")

RATIO, BS = 0.05, 128
# ragged on purpose: 8192 = aligned head only (8*128*8 tile multiple),
# 4097 = head + 1-element tail, (33, 7) and (3,) = tail-only leaves
SHAPES = ((8192,), (4097,), (33, 7), (3,))


def _pair(seed=0, vdtype=jnp.float32, shapes=SHAPES):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2 * len(shapes))
    theta = {f"w{i}": jax.random.normal(ks[2 * i], s)
             for i, s in enumerate(shapes)}
    v = {f"w{i}": (0.1 * jax.random.normal(ks[2 * i + 1], s)).astype(vdtype)
         for i, s in enumerate(shapes)}
    return theta, v


def _codecs(spec, fused=True, **kw):
    base = parse_pipeline(spec, ratio=RATIO, block_size=BS, **kw)
    return (FusedCodec.wrap(base, fused=fused),
            FusedCodec.wrap(base, fused=False))


def _payload_leaves(codec, theta, v):
    enc = jax.jit(lambda t, vv, k: codec.encode_pair(t, vv, k))
    return jax.tree.leaves(enc(theta, v, KEY))


def _assert_payloads_bitwise(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# fused vs two-pass oracle: bitwise, per eligible pipeline
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["block_topk", "block_topk|sign",
                                  "block_topk|qsgd"])
@pytest.mark.parametrize("vdtype", [jnp.float32, jnp.bfloat16])
def test_fused_bitwise_matches_two_pass_oracle(spec, vdtype):
    theta, v = _pair(vdtype=vdtype)
    fused, oracle = _codecs(spec)
    _assert_payloads_bitwise(_payload_leaves(fused, theta, v),
                             _payload_leaves(oracle, theta, v))
    # and through decode: the round functions consume the decoded delta
    pf = jax.jit(lambda t, vv, k: fused.decode(
        fused.encode_pair(t, vv, k)))(theta, v, KEY)
    po = jax.jit(lambda t, vv, k: oracle.decode(
        oracle.encode_pair(t, vv, k)))(theta, v, KEY)
    for x, y in zip(jax.tree.leaves(pf), jax.tree.leaves(po)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fused_bitwise_under_vmap():
    """Per-node batched encode (how the rounds call it) stays bitwise."""
    K = 3
    theta, v = _pair()
    theta = jax.tree.map(lambda x: jnp.stack([x + i for i in range(K)]),
                         theta)
    v = jax.tree.map(lambda x: jnp.stack([x] * K), v)
    keys = jax.random.split(KEY, K)
    fused, oracle = _codecs("block_topk|qsgd")
    pf = jax.jit(jax.vmap(fused.encode_pair))(theta, v, keys)
    po = jax.jit(jax.vmap(oracle.encode_pair))(theta, v, keys)
    _assert_payloads_bitwise(jax.tree.leaves(pf), jax.tree.leaves(po))


def test_encode_pair_matches_encode_of_materialized_delta():
    """The (theta, v) seam itself is sound: the oracle's encode_pair equals
    plain encode of the materialized residual."""
    theta, v = _pair(vdtype=jnp.bfloat16)
    _, oracle = _codecs("block_topk|qsgd")
    delta = jax.tree.map(lambda t, vv: t - vv.astype(t.dtype), theta, v)
    a = jax.jit(lambda t, vv, k: oracle.encode_pair(t, vv, k))(theta, v, KEY)
    b = jax.jit(lambda d, k: oracle.encode(d, k))(delta, KEY)
    _assert_payloads_bitwise(jax.tree.leaves(a), jax.tree.leaves(b))


# --------------------------------------------------------------------------
# transparent fallback
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["qsgd", "topk", "topk|qsgd", "sign"])
def test_ineligible_pipelines_fall_back_to_two_pass(spec):
    """No Pallas block-top-k stage 0 -> fused flag is a no-op (bitwise)."""
    theta, v = _pair()
    fused, oracle = _codecs(spec)
    assert fused.stages == oracle.stages   # _lower_stage0 left them alone
    _assert_payloads_bitwise(_payload_leaves(fused, theta, v),
                             _payload_leaves(oracle, theta, v))


def test_passthrough_leaves_fall_back():
    """min_dense_size leaves ship the dense residual in both modes."""
    theta, v = _pair()
    fused, oracle = _codecs("block_topk|qsgd", min_dense_size=300)
    pf = jax.jit(lambda t, vv, k: fused.encode_pair(t, vv, k))(theta, v, KEY)
    assert pf.specs[2].passthrough and pf.specs[3].passthrough
    np.testing.assert_array_equal(
        np.asarray(pf.entries[3].wire),
        np.asarray(theta["w3"] - v["w3"].astype(theta["w3"].dtype)))
    _assert_payloads_bitwise(jax.tree.leaves(pf),
                             _payload_leaves(oracle, theta, v))


# --------------------------------------------------------------------------
# per-layer adaptive pipelines
# --------------------------------------------------------------------------

def _per_layer(fused=True):
    kw = dict(ratio=RATIO, block_size=BS)
    base = parse_pipeline("block_topk|qsgd", **kw)
    rules = (("w0", parse_pipeline("block_topk", **kw)),
             ("w1", parse_pipeline("qsgd", **kw)))
    from repro.core.compression import _lower_stage0
    return PerLayerPipeline(
        stages=_lower_stage0(base.stages), min_dense_size=0,
        fused=fused,
        rules=tuple((p, dataclasses.replace(r,
                                            stages=_lower_stage0(r.stages)))
                    for p, r in rules))


def test_per_layer_routing_and_self_describing_decode():
    theta, v = _pair()
    pipe = _per_layer()
    payload = jax.jit(lambda t, vv, k: pipe.encode_pair(t, vv, k))(
        theta, v, KEY)
    # routing: w0 -> block_topk only, w1 -> qsgd only, rest -> base
    assert [s.name for s in leaf_stages(payload, 0)] == ["block_topk"]
    assert [s.name for s in leaf_stages(payload, 1)] == ["qsgd"]
    assert [s.name for s in leaf_stages(payload, 2)] == ["block_topk",
                                                         "qsgd"]
    # per-leaf stages recorded only where they deviate from the base
    assert payload.specs[0].stages and payload.specs[1].stages
    assert payload.specs[2].stages == ()
    # qsgd-only leaf ships a dense int grid (no sparsify)
    assert payload.entries[1].wire.size == theta["w1"].size
    out = jax.jit(pipe.decode)(payload)
    for name in theta:
        assert out[name].shape == theta[name].shape
        assert out[name].dtype == theta[name].dtype
    # routed leaves are bitwise what their own pipeline would produce
    solo = FusedCodec.wrap(parse_pipeline("block_topk", ratio=RATIO,
                                          block_size=BS))
    ref = jax.jit(lambda t, vv, k: solo.encode_pair(t, vv, k))(
        {"w0": theta["w0"]}, {"w0": v["w0"]},
        jax.random.split(KEY, 4)[0])
    np.testing.assert_array_equal(np.asarray(payload.entries[0].wire),
                                  np.asarray(ref.entries[0].wire))


def test_per_layer_fused_matches_two_pass_oracle():
    theta, v = _pair(vdtype=jnp.bfloat16)
    _assert_payloads_bitwise(_payload_leaves(_per_layer(True), theta, v),
                             _payload_leaves(_per_layer(False), theta, v))


def test_parse_layer_rules():
    assert parse_layer_rules("embed=qsgd; *=block_topk|qsgd") == (
        ("embed", "qsgd"), ("*", "block_topk|qsgd"))
    assert parse_layer_rules("") == ()
    with pytest.raises(ValueError):
        parse_layer_rules("embed")
    with pytest.raises(ValueError):
        parse_layer_rules("embed=")


def test_make_compressor_composes_fused_and_rules():
    fed = FedConfig(compressor="block_topk|qsgd", fused_compress=True,
                    layer_pipelines=(("w0", "block_topk"),),
                    compress_ratio=RATIO, block_size=BS)
    comp = make_compressor(fed)
    assert isinstance(comp, PerLayerPipeline) and comp.fused
    # stage 0 lowered to the Pallas pack path everywhere (slot-order parity)
    assert comp.stages[0].use_pallas
    assert comp.rules[0][1].stages[0].use_pallas
    # flag off -> plain pipeline, jnp stage 0 (bitwise legacy path)
    plain = make_compressor(dataclasses.replace(
        fed, fused_compress=False, layer_pipelines=()))
    assert not isinstance(plain, FusedCodec)
    assert not plain.stages[0].use_pallas


# --------------------------------------------------------------------------
# engine trajectories: the fused flag changes traffic, not results
# --------------------------------------------------------------------------

K, L, M, DIM = 4, 3, 5, 24


def linear_loss(params, batch, key):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), ()


def _shards(sizes=(17, 20, 20, 13)):
    rng = np.random.default_rng(0)
    out = []
    for n in sizes:
        x = rng.normal(size=(n, DIM)).astype(np.float32)
        w = np.arange(1.0, DIM + 1.0, dtype=np.float32) / DIM
        out.append({"x": x, "y": (x @ w).astype(np.float32)})
    return out


def _run_engine(engine_name, fused, rounds=8, s=4):
    fed = FedConfig(num_nodes=K, local_steps=L, eta=5e-3, zeta=0.3,
                    burn_in=4, compressor="block_topk|qsgd",
                    compress_ratio=0.25, block_size=64, topology="ring",
                    algorithm="cdbfl")
    topo = build_topology(resolve_topology(fed), K)
    comp = make_compressor(dataclasses.replace(fed, fused_compress=True))
    if not fused:
        comp = dataclasses.replace(comp, fused=False)   # two-pass oracle
    kwargs, shard_ctx = {}, None
    if engine_name == "shard":
        from repro.core import ShardContext
        from repro.launch.mesh import make_fed_mesh
        kwargs = dict(mesh=make_fed_mesh(s))
        shard_ctx = ShardContext("fed", s)
    rf = make_round_fn("cdbfl", linear_loss, fed, topo.omega, comp,
                       data_scale=10.0, shard_ctx=shard_ctx)
    dshards = DeviceShards.from_shards(_shards())
    eng = make_engine(engine_name, rf, dshards, L, M, bank=None,
                      chunk=4, **kwargs)
    state = init_fed_state({"w": jnp.zeros((DIM,))}, fed, key=KEY)
    state, key, bank, losses, cons = eng.run(state, jax.random.PRNGKey(1),
                                             None, rounds)
    return state, losses, cons


@pytest.mark.parametrize("engine_name", ["host", "scan"])
def test_engine_trajectory_bitwise_invariant(engine_name):
    s_f, loss_f, cons_f = _run_engine(engine_name, fused=True)
    s_o, loss_o, cons_o = _run_engine(engine_name, fused=False)
    for a, b in zip(jax.tree.leaves(s_f.params), jax.tree.leaves(s_o.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_f.v), jax.tree.leaves(s_o.v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(loss_f), np.asarray(loss_o))
    np.testing.assert_array_equal(np.asarray(cons_f), np.asarray(cons_o))


@needs4
def test_shard_engine_trajectory_bitwise_invariant():
    s_f, loss_f, cons_f = _run_engine("shard", fused=True)
    s_o, loss_o, cons_o = _run_engine("shard", fused=False)
    for a, b in zip(jax.tree.leaves(s_f.params), jax.tree.leaves(s_o.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(loss_f), np.asarray(loss_o))


# --------------------------------------------------------------------------
# HBM ledger: the tentpole's acceptance numbers
# --------------------------------------------------------------------------

def test_ledger_fused_beats_two_pass_and_approaches_bound():
    theta = {"w": jax.ShapeDtypeStruct((256, 1024), jnp.float32),
             "e": jax.ShapeDtypeStruct((4097,), jnp.float32)}
    v = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), theta)
    fused, oracle = _codecs("block_topk|qsgd")
    f = encode_hbm_bytes(fused, theta, v)
    o = encode_hbm_bytes(oracle, theta, v)
    assert f["lower_bound_bytes"] == o["lower_bound_bytes"]
    assert o["hbm_bytes"] >= 2 * f["hbm_bytes"]          # >=2x reduction
    assert f["hbm_bytes"] <= 1.5 * f["lower_bound_bytes"]  # near the bound
    # two-pass materializes the dense residual: ~5p traffic or worse
    p_bytes = sum(int(np.prod(x.shape)) * 4 for x in jax.tree.leaves(theta))
    assert o["hbm_bytes"] >= 5 * p_bytes


def test_ledger_counts_are_static_ints():
    theta, v = _pair()
    fused, _ = _codecs("block_topk")
    got = encode_hbm_bytes(fused, theta, v)
    assert all(isinstance(x, int) and x > 0 for x in got.values())
    # same numbers from shapes alone (ShapeDtypeStruct trees)
    spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), theta)
    vspec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), v)
    assert encode_hbm_bytes(fused, spec, vspec) == got


# --------------------------------------------------------------------------
# int8 posterior bank
# --------------------------------------------------------------------------

def _params(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"w": jax.random.normal(ks[0], (4, 16)),
            "b": jax.random.normal(ks[1], (4,))}


def test_int8_bank_roundtrip_error_bound():
    bank = DeviceSampleBank(burn_in=0, capacity=3, store_dtype="int8")
    st = bank.init(_params())
    st = bank.update(st, 0, _params())
    got = bank.stacked(st)
    want = _params()
    for name in want:
        w = np.asarray(want[name], np.float32)
        g = np.asarray(got[name][0])
        # symmetric absmax grid: error <= scale/2 per leading row
        amax = np.max(np.abs(w), axis=tuple(range(1, w.ndim))) \
            if w.ndim > 1 else np.abs(w)
        tol = (amax / 127.0) / 2 + 1e-7
        err = np.max(np.abs(g - w), axis=tuple(range(1, w.ndim))) \
            if w.ndim > 1 else np.abs(g - w)
        assert np.all(err <= tol)


def test_int8_bank_matches_f32_ring_semantics():
    f32 = DeviceSampleBank(burn_in=2, capacity=3, thin=2)
    i8 = DeviceSampleBank(burn_in=2, capacity=3, thin=2, store_dtype="int8")
    s32, s8 = f32.init(_params()), i8.init(_params())
    for t in range(10):
        p = jax.tree.map(lambda x: x + t, _params(t))
        s32 = f32.update(s32, t, p)
        s8 = i8.update(s8, t, p)
    assert int(s32.count) == int(s8.count)
    assert f32.length(s32) == i8.length(s8)
    np.testing.assert_array_equal(f32.order(s32), i8.order(s8))
    assert s8.slots["w"].dtype == jnp.int8
    a = np.asarray(f32.stacked(s32)["w"])
    b = np.asarray(i8.stacked(s8)["w"])
    assert a.shape == b.shape
    rel = np.max(np.abs(a - b)) / np.max(np.abs(a))
    assert rel < 1e-2


def test_int8_bank_pspecs_and_jit():
    from jax.sharding import PartitionSpec as P
    bank = DeviceSampleBank(burn_in=0, capacity=2, store_dtype="int8")
    st = bank.init(_params())
    sp = bank.pspecs(st, "fed")
    assert sp.slots["w"] == P(None, "fed")
    assert sp.scales["w"] == P(None, "fed")
    assert sp.scales["b"] == P(None, "fed")
    st2 = jax.jit(bank.update)(st, jnp.int32(0), _params())
    assert int(st2.count) == 1


def test_bank_rejects_unknown_store_dtype():
    with pytest.raises(ValueError):
        DeviceSampleBank(burn_in=0, store_dtype="float16")
