"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + allclose."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compression import QSGDCodec, _qsgd_omega
from repro.kernels import interpret_mode, ops, ref
from repro.kernels.fused_update import fused_update_pallas
from repro.kernels.pack import pack_topk_pallas, unpack_topk_pallas

KEY = jax.random.PRNGKey(0)

SHAPES = [(1024,), (8, 1024), (3, 1000, 7), (4097,), (128, 130)]
DTYPES = [jnp.float32, jnp.bfloat16]


def test_interpret_mode_follows_the_platform(monkeypatch):
    assert interpret_mode() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert interpret_mode() is True


def _pack_unpack(x2d, k):
    """The dense (rows, block) image of the kernel's packed selection."""
    vals, idx = pack_topk_pallas(x2d, k, interpret=True)
    return unpack_topk_pallas(vals, idx, x2d.shape[1], interpret=True)


# --------------------------------------------------------------------------
# block top-k selection: pack -> unpack vs the references
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("ratio", [0.01, 0.1])
def test_block_topk_sweep(shape, dtype, ratio):
    x = jax.random.normal(KEY, shape, dtype)
    vals, idx = ops.block_topk_pack(x, ratio=ratio, block_size=1024)
    got = ops.block_topk_unpack(vals, idx, x.size, shape, block_size=1024)
    x2d, n = ops._pad_to_2d(x, 1024, 8)
    k = max(1, int(np.ceil(ratio * 1024)))
    want2d = ref.block_topk_bisect_ref(x2d, k)
    want = ops._unpad(want2d, n, shape)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0)


@given(seed=st.integers(0, 40))
def test_block_topk_matches_exact_sort_semantics(seed):
    """Bisection == exact top-k when magnitudes are distinct."""
    x2d = jax.random.normal(jax.random.PRNGKey(seed), (8, 512))
    got = _pack_unpack(x2d, k=32)
    want = ref.block_topk_ref(x2d, k=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_block_topk_keeps_exactly_k_per_block():
    x2d = jax.random.normal(KEY, (16, 1024))
    out = _pack_unpack(x2d, k=10)
    nnz = np.asarray((out != 0).sum(axis=1))
    np.testing.assert_array_equal(nnz, np.full(16, 10))


def test_block_topk_kernel_exact_k_under_ties():
    """Regression: tied magnitudes must not exceed the sparsity budget the
    wire accounting charges — exactly k survive, lowest indices win, and
    the packed payload round-trips to both references' dense output."""
    row = np.zeros(256, np.float32)
    row[0], row[1], row[2] = 1.0, 1.0, 5.0
    x2d = jnp.asarray(np.tile(row, (8, 1)))
    for x in (jnp.ones((8, 256)), x2d):
        out = np.asarray(_pack_unpack(x, k=2))
        np.testing.assert_array_equal((out != 0).sum(axis=1), np.full(8, 2))
        np.testing.assert_array_equal(out, np.asarray(
            ref.block_topk_bisect_ref(x, 2)))
        np.testing.assert_array_equal(out, np.asarray(
            ref.block_topk_ref(x, 2)))


# --------------------------------------------------------------------------
# wire-format pack / unpack (kernels/pack.py)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("ratio", [0.01, 0.1])
def test_pack_unpack_roundtrip_matches_dense_kernel(shape, ratio):
    """Pack's values and block-local indices are the reference selection
    of every real block: the ``top_k`` index set (the zero-padded tail
    block's ties at 0 go to the lowest indices), the leaf's values at
    those indices, and no row for the tile padding."""
    x = jax.random.normal(KEY, shape)
    vals, idx = ops.block_topk_pack(x, ratio=ratio, block_size=1024)
    k = max(1, int(np.ceil(ratio * 1024)))
    nb = -(-x.size // 1024)
    assert vals.shape == idx.shape == (nb, k) and idx.dtype == jnp.uint16
    x2d = np.asarray(ops._pad_to_2d(x, 1024, 8)[0])[:nb]
    _, want_idx = jax.lax.top_k(jnp.abs(jnp.asarray(x2d)), k)
    idx_np = np.asarray(idx).astype(np.int64)
    np.testing.assert_array_equal(np.sort(idx_np, axis=1),
                                  np.sort(np.asarray(want_idx), axis=1))
    np.testing.assert_array_equal(np.asarray(vals),
                                  np.take_along_axis(x2d, idx_np, axis=1))


def test_pack_selects_topk_set():
    """Packed (idx, vals) pairs are exactly the top-k set of each block
    (slot order is two-tier — definite survivors then ties — so compare
    as sets), with consistent values and block-local indices."""
    x2d = jax.random.normal(KEY, (8, 512))
    k = 16
    vals, idx = pack_topk_pallas(x2d, k, interpret=True)
    assert idx.dtype == jnp.int32 and vals.shape == (8, k)
    idx_np = np.asarray(idx)
    assert (idx_np >= 0).all() and (idx_np < 512).all()  # block-local
    np.testing.assert_allclose(np.asarray(vals),
                               np.take_along_axis(np.asarray(x2d), idx_np,
                                                  axis=1), atol=0)
    _, want_idx = jax.lax.top_k(jnp.abs(x2d), k)
    for r in range(8):
        assert set(idx_np[r]) == set(np.asarray(want_idx)[r])


def test_pack_exact_k_under_ties():
    """All-tied block: exactly k packed, lowest indices win (same rule as
    jax.lax.top_k)."""
    x2d = jnp.ones((8, 256))
    vals, idx = pack_topk_pallas(x2d, 5, interpret=True)
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.tile(np.arange(5), (8, 1)))
    np.testing.assert_array_equal(np.asarray(vals), np.ones((8, 5)))


def test_pack_ties_cannot_evict_definite_survivors():
    """Regression: a tied-at-threshold group before a strictly larger
    entry must not push it out of the packed slots. Block [1, 1, 5, 0...]
    with k=2 keeps {5.0, first 1.0}, like jax.lax.top_k."""
    row = np.zeros(256, np.float32)
    row[0], row[1], row[2] = 1.0, 1.0, 5.0
    x2d = jnp.asarray(np.tile(row, (8, 1)))
    vals, idx = pack_topk_pallas(x2d, 2, interpret=True)
    for r in range(8):
        got = dict(zip(np.asarray(idx)[r].tolist(),
                       np.asarray(vals)[r].tolist()))
        assert got == {2: 5.0, 0: 1.0}


# --------------------------------------------------------------------------
# fused Eq. 9 update
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_update_sweep(shape, dtype):
    ks = jax.random.split(KEY, 4)
    th, vb, v, xi = [jax.random.normal(k, shape, dtype) for k in ks]
    got = ops.fused_update(th, vb, v, xi, zeta=0.03, noise_scale=0.014)
    want = ref.fused_update_ref(th, vb, v, xi, 0.03, 0.014)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


def test_tree_fused_update_ragged_leaves():
    """Satellite: pytree entry point on leaves that stress the padding
    wrappers — non-multiples of the (256, 128) tile, 1-element and scalar
    leaves, and a zero-size leaf (which must pass through untouched: a
    zero-row pallas grid is ill-formed)."""
    shapes = [(4097,), (3, 5), (1,), (), (0,), (128, 130), (7, 0, 3)]
    ks = jax.random.split(KEY, 4)
    trees = [
        {f"leaf{i}": jax.random.normal(jax.random.fold_in(k, i), s)
         for i, s in enumerate(shapes)}
        for k in ks
    ]
    th, vb, v, xi = trees
    got = ops.tree_fused_update(th, vb, v, xi, zeta=0.03, noise_scale=0.014)
    for i, s in enumerate(shapes):
        leaf = f"leaf{i}"
        want = ref.fused_update_ref(th[leaf], vb[leaf], v[leaf], xi[leaf],
                                    0.03, 0.014)
        assert got[leaf].shape == s and got[leaf].dtype == th[leaf].dtype
        np.testing.assert_allclose(np.asarray(got[leaf]), np.asarray(want),
                                   atol=1e-6)


def test_tree_fused_update_mixed_dtype_leaves():
    """bfloat16 leaves ride the same pytree as f32 leaves; each matches
    the reference at its own dtype."""
    shapes = [((513,), jnp.bfloat16), ((130,), jnp.float32)]
    ks = jax.random.split(KEY, 4)
    trees = [[jax.random.normal(jax.random.fold_in(k, i), s, d)
              for i, (s, d) in enumerate(shapes)] for k in ks]
    th, vb, v, xi = trees
    got = ops.tree_fused_update(th, vb, v, xi, zeta=0.5, noise_scale=0.01)
    for i, (s, d) in enumerate(shapes):
        want = ref.fused_update_ref(th[i], vb[i], v[i], xi[i], 0.5, 0.01)
        assert got[i].dtype == d
        np.testing.assert_allclose(
            np.asarray(got[i], np.float32), np.asarray(want, np.float32),
            atol=1e-2 if d == jnp.bfloat16 else 1e-6)


@given(zeta=st.floats(0.0, 1.0), ns=st.floats(0.0, 0.1))
@settings(max_examples=10)
def test_fused_update_params(zeta, ns):
    ks = jax.random.split(KEY, 4)
    th, vb, v, xi = [jax.random.normal(k, (256, 128)) for k in ks]
    got = fused_update_pallas(th, vb, v, xi, zeta, ns, interpret=True)
    want = ref.fused_update_ref(th, vb, v, xi, zeta, ns)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# --------------------------------------------------------------------------
# QSGD of the packed carrier (kernels/fused_compress.py grid_quant)
# --------------------------------------------------------------------------

def _carrier(shape):
    """A leaf shape as a packed ``(rows, k)`` carrier of its elements."""
    return (int(np.prod(shape[:-1])), shape[-1])


def _carrier_kernel(x, key, levels):
    return jax.jit(lambda c, kk: ops.qsgd_quantize_carrier(
        c, kk, levels=levels,
        out_dtype=QSGDCodec(levels=levels)._wire_dtype()))(x, key)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("levels", [4, 16, 64])
def test_qsgd_sweep(shape, levels):
    """The carrier kernel's integer grid and scale equal the codec
    stage's encode bit for bit, for every level count."""
    x = jax.random.normal(KEY, _carrier(shape))
    grid, norm = _carrier_kernel(x, KEY, levels)
    want, aux = jax.jit(
        lambda c, kk: QSGDCodec(levels=levels).encode(c, kk)[:2])(x, KEY)
    assert grid.dtype == want.dtype == jnp.int8 and grid.shape == x.shape
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(norm).reshape(1),
                                  np.asarray(aux["scale"]))
    assert int(np.abs(np.asarray(grid, np.int32)).max()) <= levels


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_qsgd_kernel_bitwise_vs_codec_stage(shape, dtype):
    """Satellite: decoded, the carrier kernel's grid is the codec stage's
    ``decode(encode(x))`` bit for bit, on float32 and bfloat16 carriers
    (under a common jit context — eager codec calls differ in the last
    ulp because XLA folds the constant divisors differently outside
    jit)."""
    stage = QSGDCodec(levels=16)
    x = jax.random.normal(KEY, _carrier(shape), dtype)

    @jax.jit
    def via_kernel(c, kk):
        grid, norm = ops.qsgd_quantize_carrier(
            c, kk, levels=16, out_dtype=stage._wire_dtype())
        _, _, meta = stage.encode(c, kk)
        return stage.decode(grid, {"scale": norm.reshape(1)}, meta)

    got = via_kernel(x, KEY)
    want = jax.jit(lambda c, kk: stage.decode(*stage.encode(c, kk)))(x, KEY)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_qsgd_carrier_int16_grid():
    """Above 127 levels the wire grid widens to int16, and the carrier
    kernel still equals the codec stage's encode bit for bit (one
    dominant survivor puts its grid point past int8's range)."""
    x = jax.random.normal(KEY, (8, 64)).at[3, 5].set(-100.0)
    grid, norm = _carrier_kernel(x, KEY, 200)
    want, aux = jax.jit(
        lambda c, kk: QSGDCodec(levels=200).encode(c, kk)[:2])(x, KEY)
    assert grid.dtype == want.dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(norm).reshape(1),
                                  np.asarray(aux["scale"]))
    assert int(np.abs(np.asarray(grid, np.int32)).max()) > 127


def test_qsgd_quantization_grid():
    """Decoded outputs live on the (1/(1+omega))-scaled {0, ±norm/s, ...}
    grid."""
    x = jax.random.normal(KEY, (8, 64))
    levels = 8
    omega = _qsgd_omega(512, levels)
    grid, norm = _carrier_kernel(x, KEY, levels)
    _, _, meta = QSGDCodec(levels=levels).encode(x, KEY)
    out = np.asarray(QSGDCodec(levels=levels).decode(
        grid, {"scale": norm.reshape(1)}, meta), np.float64)
    q = out * levels / float(jnp.linalg.norm(x)) * (1.0 + omega)
    np.testing.assert_allclose(q, np.round(q), atol=1e-4)
    np.testing.assert_allclose(np.round(q), np.asarray(grid), atol=0)


# --------------------------------------------------------------------------
# padding helpers
# --------------------------------------------------------------------------

@given(n=st.integers(1, 5000))
@settings(max_examples=20)
def test_pad_unpad_roundtrip(n):
    x = jnp.arange(n, dtype=jnp.float32)
    x2d, n_ = ops._pad_to_2d(x, 128, 8)
    assert x2d.shape[0] % 8 == 0 and x2d.shape[1] == 128
    back = ops._unpad(x2d, n_, (n,))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
