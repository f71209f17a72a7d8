"""Property tests for the compression operators Q (paper Eq. 6)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core.compression import FusedCodec, parse_pipeline

KEY = jax.random.PRNGKey(0)


def _rand_tree(seed, shapes=((64,), (33, 7), (128, 130))):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {f"w{i}": jax.random.normal(k, s)
            for i, (k, s) in enumerate(zip(ks, shapes))}


def _lowered(spec, **kw):
    """The pipeline with stage 0 on the Pallas pack path, as the fused
    rounds run it (two-pass: the residual is materialized)."""
    return FusedCodec.wrap(parse_pipeline(spec, **kw), fused=False)


ALL_CODECS = (
    [pytest.param(s, False, id=s)
     for s in ("identity", "topk", "block_topk", "randk", "sign", "qsgd")]
    + [pytest.param(s, True, id=f"{s}-lowered")
       for s in ("block_topk", "block_topk|qsgd")])


@pytest.mark.parametrize("spec,lowered", ALL_CODECS)
def test_shapes_and_dtypes_preserved(spec, lowered):
    """Every leaf decodes at its own shape and dtype, bfloat16 included."""
    tree = _rand_tree(0)
    tree["w3"] = jax.random.normal(KEY, (300,), jnp.bfloat16)
    make = _lowered if lowered else parse_pipeline
    out = make(spec, ratio=0.1, block_size=128)(tree, KEY)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.shape == b.shape
        assert a.dtype == b.dtype


@given(seed=st.integers(0, 50), ratio=st.sampled_from([0.01, 0.05, 0.25]))
def test_topk_sparsity_budget(seed, ratio):
    x = jax.random.normal(jax.random.PRNGKey(seed), (4096,))
    out = parse_pipeline("topk", ratio=ratio)({"w": x}, KEY)["w"]
    k = int(np.ceil(ratio * 4096))
    nnz = int(jnp.sum(out != 0))
    assert nnz <= k + 8  # ties tolerance
    # kept entries are the largest magnitudes
    kept = jnp.abs(x)[out != 0]
    dropped = jnp.abs(x)[out == 0]
    if len(kept) and len(dropped):
        assert float(kept.min()) >= float(dropped.max()) - 1e-6


@given(seed=st.integers(0, 50))
def test_block_topk_matches_global_within_block(seed):
    """Each block keeps exactly its own top-k (distinct magnitudes)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, 128))
    comp = parse_pipeline("block_topk", ratio=0.1, block_size=128)
    out = comp({"w": x.reshape(-1)}, KEY)["w"].reshape(4, 128)
    k = int(np.ceil(0.1 * 128))
    for b in range(4):
        nnz = int(jnp.sum(out[b] != 0))
        assert nnz == k


@given(seed=st.integers(0, 30))
def test_qsgd_mean_proportional_to_x(seed):
    """The scaled QSGD satisfies E[Q(x)] = x/(1+omega) — unbiased up to the
    contraction scaling (the CHOCO control sequences absorb the factor)."""
    from repro.core.compression import _qsgd_omega
    x = jax.random.normal(jax.random.PRNGKey(seed), (256,))
    comp = parse_pipeline("qsgd", qsgd_levels=8)
    acc = jnp.zeros_like(x)
    n = 64
    for i in range(n):
        acc = acc + comp({"w": x}, jax.random.PRNGKey(1000 + i))["w"]
    mean = acc / n * (1.0 + _qsgd_omega(256, 8))
    err = float(jnp.linalg.norm(mean - x) / jnp.linalg.norm(x))
    assert err < 0.15


def test_wire_bytes_99_percent_saving():
    """The paper's headline: top-k @1% cuts ~99% of the payload bytes."""
    tree = {"w": jnp.zeros((2_700_000,))}  # the paper's p=2.7M
    dense = parse_pipeline("identity").wire_bytes(tree)
    comp = parse_pipeline("topk", ratio=0.01).wire_bytes(tree)
    assert dense == 2_700_000 * 4
    saving = 1 - comp / dense
    assert saving > 0.97  # values+indices overhead keeps it just under 99%


def test_pallas_matches_reference_block_topk():
    x = jax.random.normal(KEY, (8 * 1024,))
    a = parse_pipeline("block_topk", ratio=0.05, block_size=1024)({"w": x},
                                                                  KEY)
    b = _lowered("block_topk", ratio=0.05, block_size=1024)({"w": x}, KEY)
    np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]),
                               atol=1e-6)


def test_topk_exact_k_under_ties():
    """Tied magnitudes must not exceed the sparsity budget: exactly k kept,
    ties broken deterministically toward the lower index."""
    x = jnp.concatenate([jnp.ones((16,)), 0.25 * jnp.ones((16,))])
    comp = parse_pipeline("topk", ratio=0.25)        # k = 8 of 32
    out = comp({"w": x}, KEY)["w"]
    assert int(jnp.sum(out != 0)) == 8
    # deterministic: the 8 lowest-index entries of the tied top group
    np.testing.assert_array_equal(np.flatnonzero(np.asarray(out)),
                                  np.arange(8))


def test_block_topk_exact_k_under_ties():
    x = jnp.ones((4 * 128,))                         # all tied, 4 blocks
    comp = parse_pipeline("block_topk", ratio=0.1, block_size=128)
    out = comp({"w": x}, KEY)["w"].reshape(4, 128)
    k = int(np.ceil(0.1 * 128))
    for b in range(4):
        row = np.asarray(out[b])
        assert int((row != 0).sum()) == k
        np.testing.assert_array_equal(np.flatnonzero(row), np.arange(k))


def test_wire_bytes_pallas_matches_reference():
    """The Pallas pack path must report block-local 2-byte indices, like
    the jnp block top-k: k per block, 4 + 2 bytes each."""
    tree = {"w": jnp.zeros((100_000,))}
    ref = parse_pipeline("block_topk", ratio=0.01).wire_bytes(tree)
    pal = _lowered("block_topk", ratio=0.01).wire_bytes(tree)
    assert pal == ref
    nb, k = -(-100_000 // 1024), int(np.ceil(0.01 * 1024))
    assert ref == nb * k * (4 + 2)
