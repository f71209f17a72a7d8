"""The fused causal attention kernel (``repro.kernels.flash_attention``)
against ``attention()``'s naive masked path, in Pallas interpret mode, and
the dispatch that picks it.

``attention()`` takes the kernel only on a TPU. Here the dispatch's platform
check is patched, so the kernel runs in the interpreter; q, k and v are
handed to ``attention()`` directly (its QKV products and RoPE are patched
out, the output product is the identity), so the outputs and gradients
compared are those of the attention itself.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig
from repro.kernels import flash_attention
from repro.models import attention

H, KV = 9, 3                              # GQA: 3 query heads a KV head
BF16_EPS = 2.0 ** -8                      # bfloat16's unit roundoff


def _cfg(hd, **kw):
    return ModelConfig(d_model=H * hd, num_heads=H, num_kv_heads=KV,
                       head_dim=hd, **kw)


def _qkv(s, hd, dtype, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(s + hd), 4)
    q = jax.random.normal(ks[0], (batch, s, H, hd))
    k = jax.random.normal(ks[1], (batch, s, KV, hd))
    v = jax.random.normal(ks[2], (batch, s, KV, hd))
    ct = jax.random.normal(ks[3], (batch, s, H * hd))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), ct


def _attend(monkeypatch, cfg, kernel, **kw):
    """(q, k, v) -> attention()'s context (B, S, H*hd): the QKV products
    patched out, the output product the identity, the platform check
    patched to pick the kernel path or not."""
    monkeypatch.setattr(attention, "interpret_mode", lambda: not kernel)
    hd = cfg.resolved_head_dim
    wo = jnp.eye(H * hd).reshape(H, hd, H * hd)

    def f(q, k, v):
        monkeypatch.setattr(attention, "_qkv", lambda *a: (q, k, v))
        x = jnp.zeros(q.shape[:2] + (cfg.d_model,), q.dtype)
        return attention.attention({"wo": wo.astype(q.dtype)}, x, None, cfg,
                                   **kw)
    return f


def _grads(f, q, k, v, ct):
    return jax.grad(lambda q, k, v: jnp.sum(
        f(q, k, v).astype(jnp.float32) * ct), argnums=(0, 1, 2))(q, k, v)


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("s", [256, 512])
def test_kernel_path_matches_naive_attention(monkeypatch, dtype, hd, s):
    """Output and gradients w.r.t. q, k and v of the kernel path against the
    naive masked path taken in float32 on the same values. float32 inputs
    agree to float32 rounding; bfloat16 inputs to two bfloat16 roundings of
    the largest magnitude (the kernel's output and gradients are stored in
    bfloat16, and its probabilities enter the PV and dV products in it)."""
    q, k, v, ct = _qkv(s, hd, jnp.dtype(dtype))
    naive = _attend(monkeypatch, _cfg(hd, attn_impl="naive"), kernel=False)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    ref, ref_g = naive(*f32), _grads(naive, *f32, ct)
    kernel = _attend(monkeypatch, _cfg(hd), kernel=True)
    before = attention.flash_attention_traces
    out, grads = kernel(q, k, v), _grads(kernel, q, k, v, ct)
    assert attention.flash_attention_traces > before
    assert out.dtype == q.dtype
    for got, want in zip((out,) + grads, (ref,) + ref_g):
        scale = float(jnp.max(jnp.abs(want)))
        tol = 2e-6 * scale if dtype == "float32" else 2 * BF16_EPS * scale
        assert _err(got, want) <= tol, (_err(got, want), tol)


@pytest.mark.parametrize("hd,block", [(64, 128), (128, 256)])
def test_kernel_over_several_blocks(hd, block):
    """Blocks smaller than the sequence: key blocks past the diagonal are
    skipped, diagonal blocks masked, the running max and sum carried across
    key blocks, and dK/dV summed over the query blocks and heads."""
    s = 512
    q, k, v, ct = _qkv(s, hd, jnp.float32)
    ct = ct.reshape(q.shape)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def naive(q, k, v):
        scores = attention._gqa_scores(q, k)
        probs = jax.nn.softmax(jnp.where(mask, scores, attention.NEG_INF), -1)
        return jnp.einsum("bgrst,btgk->bsgrk", probs, v).reshape(q.shape)

    def kernel(q, k, v):
        return flash_attention.flash_gqa(q, k, v, block=block)

    for got, want in zip((kernel(q, k, v),) + _grads(kernel, q, k, v, ct),
                         (naive(q, k, v),) + _grads(naive, q, k, v, ct)):
        assert _err(got, want) <= 2e-6 * float(jnp.max(jnp.abs(want)))


def _traced(monkeypatch, cfg, s, on_tpu=True, **kw):
    """Trace attention() once and return how the two counters moved."""
    monkeypatch.setattr(attention, "interpret_mode", lambda: not on_tpu)
    params = attention.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), jnp.bfloat16)
    positions = jnp.arange(s)[None]
    flash, chunked = (attention.flash_attention_traces,
                      attention.chunked_attention_traces)
    jax.eval_shape(lambda x: attention.attention(params, x, positions, cfg,
                                                 **kw), x)
    return (attention.flash_attention_traces - flash,
            attention.chunked_attention_traces - chunked)


def test_dispatch_takes_kernel_on_tpu(monkeypatch):
    assert _traced(monkeypatch, _cfg(64, chunk_size=128), 512) == (1, 0)
    assert _traced(monkeypatch, _cfg(128, chunk_size=128), 256) == (1, 0)


@pytest.mark.parametrize("case", ["cpu", "window", "uneven", "head_dim",
                                  "attn_impl"])
def test_dispatch_falls_back_to_chunked(monkeypatch, case):
    """Off a TPU, with a sliding window, at a sequence no kernel block
    divides, at a head size the kernel does not take, or when the config
    asks for the chunked form, long sequences take ``chunked_gqa``."""
    cfg, s, kw, on_tpu = _cfg(64, chunk_size=128), 512, {}, True
    if case == "cpu":
        on_tpu = False
    elif case == "window":
        kw = {"window": 64}
    elif case == "uneven":
        cfg, s = _cfg(64, chunk_size=160), 320
    elif case == "head_dim":
        cfg = _cfg(32, chunk_size=128)
    else:
        cfg = _cfg(64, chunk_size=128, attn_impl="chunked")
    assert flash_attention.flash_block(320) is None
    assert _traced(monkeypatch, cfg, s, on_tpu=on_tpu, **kw) == (0, 1)


def test_dispatch_leaves_cross_attention_alone(monkeypatch):
    cfg = _cfg(64, chunk_size=128)
    kv = jnp.zeros((1, 512, KV, 64), jnp.bfloat16)
    assert _traced(monkeypatch, cfg, 512, cross_kv=(kv, kv)) == (0, 0)


def test_kernel_blocks_from_the_shape():
    assert [flash_attention.flash_block(s) for s in (2048, 768, 384, 200)] \
        == [512, 256, 128, None]
    assert flash_attention.supported(2048, 64, 3)
    assert flash_attention.supported(1024, 128, 8)
    assert not flash_attention.supported(2048, 32, 3)
    assert not flash_attention.supported(200, 64, 3)
    # the backward's float32 dQ of a whole sequence has to fit in VMEM
    assert not flash_attention.supported(32768, 128, 4)


def test_lm_loss_through_the_kernel(monkeypatch):
    """A two-layer dense LM's recomputed training loss and its gradients are
    the same through the kernel (interpreted) as through the naive path."""
    from repro.models import get_model
    cfg = ModelConfig(family="dense", num_layers=2, d_model=H * 64,
                      num_heads=H, num_kv_heads=KV, head_dim=64, d_ff=256,
                      vocab_size=128, dtype="float32", scan_layers=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 128)
    grad = jax.value_and_grad(lambda p: model.loss(p, {"tokens": tokens})[0])
    monkeypatch.setattr(attention, "interpret_mode", lambda: True)
    ref_loss, ref_g = grad(params)
    monkeypatch.setattr(attention, "interpret_mode", lambda: False)
    before = attention.flash_attention_traces
    loss, g = grad(params)
    assert attention.flash_attention_traces > before
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6 * float(np.max(np.abs(b))) + 1e-9)
