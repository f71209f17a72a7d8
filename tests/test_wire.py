"""Codec pipelines and the packed wire format (DESIGN.md §2).

Covers the acceptance contract of the codec layer:

* ``decode(encode(x))`` is **bitwise** identical to the dense-masked
  reference operator (``tests/codec_ref.py``) for every sparse codec (and
  qsgd, whose int8 grid reproduces the reference arithmetic exactly);
* ``measured_bytes()`` — computed from the actual packed buffers — matches
  the closed-form formula table (exactly for sparse codecs, within the
  byte-alignment of sub-byte grids for quantizers);
* the delta-contraction property holds for every operator and composed
  pipeline, with multiplicatively composed deltas;
* payloads are jit-transparent pytrees;
* per-round wire bytes are reported through RoundMetrics and agree between
  the host and scan engines.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

import codec_ref
from repro.config import FedConfig
from repro.core.compression import (CompressionPipeline, FusedCodec,
                                    _qsgd_omega, make_compressor,
                                    parse_pipeline)
from repro.utils.tree import split_key_like

KEY = jax.random.PRNGKey(0)


def _rand_tree(seed, shapes=((64,), (33, 7), (128, 130))):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {f"w{i}": jax.random.normal(k, s)
            for i, (k, s) in enumerate(zip(ks, shapes))}


def _sq(t):
    return sum(float(jnp.sum(x.astype(jnp.float32) ** 2))
               for x in jax.tree.leaves(t))


def _lowered(spec, **kw):
    """The pipeline with stage 0 on the Pallas pack path, as the fused
    rounds run it (two-pass: the residual is materialized)."""
    return FusedCodec.wrap(parse_pipeline(spec, **kw), fused=False)


# randk appears only in the expectation-averaged contraction test below:
# its kept mass fluctuates around ratio·||x||² per realization.
SINGLE = ["identity", "topk", "block_topk", "qsgd", "sign"]
COMPOSED = ["block_topk|qsgd", "block_topk|sign", "topk|qsgd"]

# dense-masked reference operator per codec, at the test's ratio/block
REFERENCE = {
    "identity": lambda x, k: x,
    "topk": lambda x, k: codec_ref.topk(x, k, ratio=0.1),
    "block_topk": lambda x, k: codec_ref.block_topk(x, k, ratio=0.1,
                                                    block_size=128),
    "randk": lambda x, k: codec_ref.randk(x, k, ratio=0.1),
    "qsgd": lambda x, k: codec_ref.qsgd(x, k, levels=16),
    "sign": codec_ref.sign,
}


# --------------------------------------------------------------------------
# Round-trip vs the dense-masked reference operators
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["identity", "topk", "block_topk", "randk",
                                  "qsgd", "sign"])
def test_roundtrip_bitwise_vs_legacy(name):
    """decode(encode(x)) == the dense-masked reference operator, bit for
    bit, under the same per-leaf keys (sign's ternary code reproduces
    sign(0)·scale = 0 exactly too)."""
    tree = _rand_tree(3)
    want = jax.tree.map(REFERENCE[name], tree, split_key_like(KEY, tree))
    pipe = parse_pipeline(name, ratio=0.1, block_size=128)
    out = pipe.decode(pipe.encode(tree, KEY))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(out)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sign_zero_symbol_regression():
    """Exact zeros must decode to 0, not ±scale — sparsified carriers pad
    blocks with zeros whenever a block has fewer than k nonzeros."""
    x = np.zeros(64, np.float32)
    x[7] = 3.0
    pipe = parse_pipeline("block_topk|sign", ratio=0.1, block_size=32)
    out = np.asarray(pipe({"w": jnp.asarray(x)}, KEY)["w"])
    assert (out[x == 0] == 0).all()
    assert out[7] != 0
    # composed support stays a subset of the sparsifier's
    sparse = np.asarray(parse_pipeline("block_topk", ratio=0.1,
                                       block_size=32)({"w": jnp.asarray(x)},
                                                      KEY)["w"])
    assert not np.any((out != 0) & (sparse == 0))


def test_pipeline_call_is_decode_encode():
    tree = _rand_tree(0)
    pipe = parse_pipeline("block_topk|qsgd", ratio=0.05, block_size=128)
    a = pipe(tree, KEY)
    b = pipe.decode(pipe.encode(tree, KEY))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_composed_sparsity_pattern_preserved():
    """Quantizing the survivors must not *add* nonzeros: the composed
    support is a subset of the sparsifier's (qsgd may round a small
    survivor onto the zero grid point, never off-pattern)."""
    tree = _rand_tree(1)
    sparse = parse_pipeline("block_topk", ratio=0.05, block_size=128)(
        tree, KEY)
    composed = parse_pipeline("block_topk|qsgd", ratio=0.05,
                              block_size=128)(tree, KEY)
    for a, b in zip(jax.tree.leaves(sparse), jax.tree.leaves(composed)):
        a_nz, b_nz = np.asarray(a) != 0, np.asarray(b) != 0
        assert not np.any(b_nz & ~a_nz)
        # and it keeps most of the pattern (zero-rounding is the tail)
        assert b_nz.sum() >= 0.5 * a_nz.sum()


def test_payload_is_jit_transparent():
    tree = _rand_tree(2)
    pipe = parse_pipeline("block_topk|qsgd", ratio=0.1, block_size=128)
    p_eager = pipe.encode(tree, KEY)
    p_jit = jax.jit(pipe.encode)(tree, KEY)
    assert p_jit.measured_bytes() == p_eager.measured_bytes()
    out_jit = jax.jit(pipe.decode)(p_jit)
    out = pipe.decode(p_eager)
    # jit and eager may fuse the dequant multipliers differently (1-ulp);
    # the bitwise contract is pipeline-vs-reference, not jit-vs-eager
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(out_jit)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("spec,lowered", [
    pytest.param("topk", False, id="topk"),
    pytest.param("block_topk", True, id="block_topk-lowered")])
def test_min_dense_size_passthrough_pipeline(spec, lowered):
    tree = {"small": jnp.ones((10,)), "big": jax.random.normal(KEY, (4096,))}
    make = _lowered if lowered else parse_pipeline
    pipe = make(spec, ratio=0.01, min_dense_size=64)
    payload = pipe.encode(tree, KEY)
    out = pipe.decode(payload)
    np.testing.assert_array_equal(np.asarray(out["small"]), np.ones(10))
    assert int(jnp.sum(out["big"] != 0)) < 4096
    # dense passthrough leaf charged at full fp32 width (dict leaves are
    # key-sorted: "big" first, "small" second)
    assert payload.per_leaf_bytes()[1] == 10 * 4


# --------------------------------------------------------------------------
# Contraction: every operator and composed pipeline
# --------------------------------------------------------------------------

# (spec, ratio, qsgd levels, stage 0 lowered onto the Pallas pack path)
CONTRACTION = (
    [pytest.param(s, 0.05, 16, False, id=s) for s in SINGLE + COMPOSED]
    + [pytest.param(s, 0.05, 16, True, id=f"{s}-lowered")
       for s in ("block_topk", "block_topk|qsgd", "block_topk|sign")]
    + [pytest.param("topk", 0.25, 16, False, id="topk-ratio0.25"),
       pytest.param("block_topk", 0.25, 16, False, id="block_topk-ratio0.25"),
       pytest.param("qsgd", 0.05, 4, False, id="qsgd-levels4")])


@pytest.mark.parametrize("spec,ratio,levels,lowered", CONTRACTION)
@given(seed=st.integers(0, 60))
def test_pipeline_contraction_property(spec, ratio, levels, lowered, seed):
    """E||Q(x) - x||² <= (1 - delta)||x||² with the shape-aware delta."""
    tree = _rand_tree(seed)
    make = _lowered if lowered else parse_pipeline
    pipe = make(spec, ratio=ratio, block_size=128, qsgd_levels=levels)
    out = pipe(tree, jax.random.PRNGKey(seed))
    err = sum(float(jnp.sum((a - b) ** 2))
              for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)))
    assert err <= (1 - pipe.delta_for(tree)) * _sq(tree) + 1e-5


@given(seed=st.integers(0, 60))
def test_randk_no_rescale_regression(seed):
    """The old 1/ratio rescale gave E||Q(x)-x||² = (1/ratio − 1)||x||² —
    a contraction violation. Biased rand-k keeps exactly k coordinates
    untouched, so the error is at most ||x||² and respects delta=ratio in
    expectation; with exactly k survivors it holds per-realization."""
    ratio = 0.05
    x = jax.random.normal(jax.random.PRNGKey(seed), (2048,))
    comp = parse_pipeline("randk", ratio=ratio)
    out = comp({"w": x}, jax.random.PRNGKey(seed + 1))["w"]
    k = int(np.ceil(ratio * 2048))
    assert int(jnp.sum(out != 0)) <= k          # exactly-k, no rescale
    kept = out[out != 0]
    orig = x[out != 0]
    np.testing.assert_allclose(np.asarray(kept), np.asarray(orig), atol=0)
    err = float(jnp.sum((out - x) ** 2))
    assert err <= float(jnp.sum(x ** 2)) + 1e-6


@pytest.mark.parametrize("spec", ["randk", "randk|qsgd"])
def test_randk_contraction_in_expectation(spec):
    """E||Q(x)-x||² <= (1 - delta)||x||², averaged over the key stream —
    the form the CHOCO analysis needs (randk is random in the index set)."""
    tree = {"w": jax.random.normal(KEY, (4096,))}
    pipe = parse_pipeline(spec, ratio=0.05, block_size=128)
    norm = _sq(tree)
    errs = []
    for i in range(48):
        out = pipe(tree, jax.random.PRNGKey(100 + i))
        errs.append(float(jnp.sum((out["w"] - tree["w"]) ** 2)))
    assert np.mean(errs) <= (1 - pipe.delta_for(tree)) * norm * 1.02


def test_randk_error_matches_dropped_mass():
    """Without rescale the error is exactly the dropped coordinates' mass."""
    x = jax.random.normal(KEY, (1024,))
    out = parse_pipeline("randk", ratio=0.25)({"w": x}, KEY)["w"]
    dropped = float(jnp.sum(jnp.where(out == 0, x, 0.0) ** 2))
    err = float(jnp.sum((out - x) ** 2))
    np.testing.assert_allclose(err, dropped, rtol=1e-6)


def test_delta_composes_multiplicatively():
    pipe = parse_pipeline("block_topk|qsgd", ratio=0.05, block_size=128)
    tree = _rand_tree(0)
    d_sparse = parse_pipeline("block_topk", ratio=0.05,
                              block_size=128).delta_for(tree)
    # qsgd acts on the packed carriers; its factor is the min over them
    assert pipe.delta_for(tree) <= d_sparse
    assert pipe.delta == pytest.approx(0.05 * 1e-3)


def test_qsgd_delta_for_replaces_placeholder():
    """delta_for computes min_leaf 1/(1+omega); the property stays as the
    conservative fallback."""
    comp = parse_pipeline("qsgd", qsgd_levels=16)
    tree = _rand_tree(0)
    want = min(1.0 / (1.0 + _qsgd_omega(int(np.prod(x.shape)), 16))
               for x in jax.tree.leaves(tree))
    assert comp.delta_for(tree) == pytest.approx(want)
    assert comp.delta == pytest.approx(1e-3)      # fallback unchanged
    # the shape-aware bound is tight enough to be useful
    assert comp.delta_for(tree) > comp.delta
    # leaves passed through dense contract with delta = 1: the bound is
    # the min over the compressed leaves only
    big = _qsgd_omega(128 * 130, 16)
    passthrough = parse_pipeline("qsgd", min_dense_size=256)
    assert passthrough.delta_for(tree) == pytest.approx(1.0 / (1.0 + big))


# --------------------------------------------------------------------------
# Wire accounting: measured (buffers) vs formula (table)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["topk", "block_topk", "randk",
                                  "block_topk|sign"])
def test_measured_equals_formula_sparse(spec):
    tree = _rand_tree(0)
    pipe = parse_pipeline(spec, ratio=0.1, block_size=128)
    payload = pipe.encode(tree, KEY)
    assert payload.measured_bytes() == pipe.formula_bytes(tree)


@pytest.mark.parametrize("spec", ["qsgd", "block_topk|qsgd"])
def test_measured_vs_formula_quantized(spec):
    """Sub-byte grids materialize byte-aligned: measured/formula is in
    [1, 8/bits] + the per-leaf scale overhead."""
    tree = _rand_tree(0)
    pipe = parse_pipeline(spec, ratio=0.1, block_size=128, qsgd_levels=16)
    payload = pipe.encode(tree, KEY)
    m, f = payload.measured_bytes(), pipe.formula_bytes(tree)
    assert f <= m <= -(-f * 8) // 6 + 4 * len(jax.tree.leaves(tree))


@pytest.mark.parametrize("n", [64, 2048, 8 * 1024])
def test_block_topk_pallas_measured_equals_formula(n):
    """Regression: the Pallas pack payload must not carry the kernel's
    ROWS_PER_TILE padding rows — measured == formula for every leaf size,
    and the round-trip matches the bisection reference."""
    from repro.kernels import ops, ref
    tree = {"w": jax.random.normal(KEY, (n,))}
    pipe = _lowered("block_topk", ratio=0.01, block_size=1024)
    payload = pipe.encode(tree, KEY)
    assert payload.measured_bytes() == pipe.formula_bytes(tree)
    x2d, _ = ops._pad_to_2d(tree["w"], 1024, 8)
    dense = ref.block_topk_bisect_ref(x2d, 11).reshape(-1)[:n]
    np.testing.assert_array_equal(np.asarray(pipe.decode(payload)["w"]),
                                  np.asarray(dense))


def test_wire_bytes_static_no_execution():
    """Pipeline.wire_bytes works from avals alone (eval_shape)."""
    specs = {"w": jax.ShapeDtypeStruct((4096,), jnp.float32)}
    pipe = parse_pipeline("block_topk|qsgd", ratio=0.01, block_size=1024)
    payload_bytes = pipe.wire_bytes(specs)
    concrete = pipe.encode({"w": jnp.zeros((4096,))}, KEY).measured_bytes()
    assert payload_bytes == concrete


def test_randk_wire_bytes_values_only():
    """randk charges values + the 8-byte key, not k·(elem+index)."""
    tree = {"w": jnp.zeros((100_000,))}
    k = int(np.ceil(0.01 * 100_000))
    pipe = parse_pipeline("randk", ratio=0.01)
    assert pipe.wire_bytes(tree) == k * 4 + 8
    assert pipe.formula_bytes(tree) == k * 4 + 8


def test_wire_payload_99_percent_saving_measured():
    """The paper's headline, now from materialized buffers: block-top-k @1%
    cuts >97% of the dense payload (values + 2-byte indices)."""
    tree = {"w": jnp.zeros((2_700_000,))}      # the paper's p=2.7M
    dense = 2_700_000 * 4
    measured = parse_pipeline("block_topk", ratio=0.01).wire_bytes(tree)
    assert 1 - measured / dense > 0.97


def test_pipeline_dsl_validation():
    with pytest.raises(ValueError):
        parse_pipeline("qsgd|topk")            # quantize before sparsify
    with pytest.raises(ValueError):
        parse_pipeline("topk|randk")           # two sparsifiers
    with pytest.raises(ValueError):
        parse_pipeline("sign|qsgd")            # quantizer not terminal
    with pytest.raises(ValueError):
        parse_pipeline("qsgd|sign")            # quantizer not terminal
    with pytest.raises(ValueError):
        parse_pipeline("block_topk|sign|qsgd")
    with pytest.raises(ValueError):
        parse_pipeline("nope")
    assert parse_pipeline("block_topk|qsgd").spec == "block_topk|qsgd"


# --------------------------------------------------------------------------
# Config / round-function integration
# --------------------------------------------------------------------------

def test_make_compressor_pipeline_precedence():
    """``compressor`` is the codec DSL: one name is a single-stage
    pipeline, ``a|b`` chains stages, and an unknown stage is refused."""
    fed = FedConfig(compressor="block_topk|qsgd", compress_ratio=0.05)
    comp = make_compressor(fed)
    assert isinstance(comp, CompressionPipeline)
    assert comp.spec == "block_topk|qsgd"
    assert comp.stages[0].ratio == 0.05
    assert make_compressor(FedConfig(compressor="topk")).spec == "topk"
    with pytest.raises(ValueError, match="unknown codec"):
        make_compressor(FedConfig(compressor="block_topk_pallas"))


def test_compressor_flag_is_the_pipeline_dsl():
    """The launcher's one codec flag takes the DSL straight into
    ``FedConfig.compressor``; the old ``--pipeline`` spelling is gone."""
    from repro.launch.train import _parse_args
    args = _parse_args(["--compressor", "block_topk|qsgd"])
    comp = make_compressor(FedConfig(compressor=args.compressor))
    assert comp.spec == "block_topk|qsgd"
    assert _parse_args([]).compressor == "block_topk"
    with pytest.raises(SystemExit):
        _parse_args(["--pipeline", "block_topk|qsgd"])


def test_round_metrics_report_wire_bytes():
    """cdbfl rounds report measured bytes/node; equal across engines."""
    from repro.core import (build_topology, init_fed_state, make_round_fn,
                            resolve_topology)
    from repro.data.partition import DeviceShards
    from repro.train.engine import make_engine

    K, L, M, DIM = 4, 2, 5, 6
    rng = np.random.default_rng(0)
    shards = [{"x": rng.normal(size=(12, DIM)).astype(np.float32),
               "y": rng.normal(size=(12,)).astype(np.float32)}
              for _ in range(K)]

    def loss(params, batch, key):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), ()

    fed = FedConfig(num_nodes=K, local_steps=L, eta=1e-3, zeta=0.3,
                    compressor="topk|qsgd", compress_ratio=0.5,
                    topology="ring", algorithm="cdbfl")
    topo = build_topology(resolve_topology(fed), K)
    comp = make_compressor(fed)
    round_fn = make_round_fn("cdbfl", loss, fed, topo.omega, comp)
    params0 = {"w": jnp.zeros((DIM,))}
    dshards = DeviceShards.from_shards(shards)

    hists = {}
    for name in ("host", "scan"):
        eng = make_engine(name, round_fn, dshards, L, M, bank=None, chunk=3)
        state = init_fed_state(params0, fed, key=KEY)
        eng.run(state, jax.random.PRNGKey(1), None, 7)
        hists[name] = eng.last_wire_history
    assert len(hists["host"]) == len(hists["scan"]) == 7
    np.testing.assert_allclose(hists["host"], hists["scan"], rtol=1e-6)
    # the value is the per-node measured payload: each node encodes its own
    # residual rows (node-decomposable compression, DESIGN.md §9)
    want = comp.wire_bytes({"w": jnp.zeros((DIM,))})
    np.testing.assert_allclose(hists["host"], np.full(7, want), rtol=1e-6)


def test_dsgld_reports_dense_wire():
    from repro.core import (build_topology, init_fed_state, make_round_fn,
                            resolve_topology)
    K, L, DIM = 3, 1, 8
    fed = FedConfig(num_nodes=K, local_steps=L, eta=1e-3,
                    topology="full", algorithm="dsgld")
    topo = build_topology(resolve_topology(fed), K)

    def loss(params, batch, key):
        return jnp.mean((batch @ params["w"]) ** 2), ()

    round_fn = jax.jit(make_round_fn("dsgld", loss, fed, topo.omega))
    state = init_fed_state({"w": jnp.zeros((DIM,))}, fed, key=KEY)
    batches = jnp.zeros((K, L, 4, DIM))
    _, m = round_fn(state, batches, KEY)
    assert float(m.wire_bytes) == DIM * 4      # dense fp32 θ per node
