"""Per-arch smoke tests (reduced configs) + decode/forward consistency +
chunked-vs-naive equivalence on real blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch, list_archs
from repro.models import get_model

KEY = jax.random.PRNGKey(0)
LM_ARCHS = [a for a in list_archs() if a != "lenet-radar"]


def _batch_for(cfg, b=2, s=32):
    if cfg.family == "lenet":
        return {"x": jnp.ones((b, *cfg.input_hw, 1)),
                "y": jnp.zeros((b,), jnp.int32)}
    if cfg.family == "audio":
        return {"frames": jax.random.normal(KEY, (b, cfg.encoder_seq_len, cfg.d_model)),
                "tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)}
    batch = {"tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "vlm" and cfg.num_image_patches:
        batch["patches"] = jax.random.normal(
            KEY, (b, cfg.num_image_patches, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", sorted(list_archs()))
def test_smoke_forward_and_train_step(arch):
    """Reduced variant: one forward + one SGD step; shapes + finite."""
    spec = get_arch(arch)
    cfg = spec.reduced
    assert cfg.num_layers <= 4 and cfg.d_model <= 512
    model = get_model(cfg)
    params = model.init(KEY)
    batch = _batch_for(cfg)
    loss, aux = jax.jit(model.loss)(params, batch)
    assert jnp.isfinite(loss), arch

    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0
    new_params = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
    loss2, _ = jax.jit(model.loss)(new_params, batch)
    assert jnp.isfinite(loss2)


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_smoke_decode_step(arch):
    spec = get_arch(arch)
    cfg = spec.reduced
    model = get_model(cfg)
    params = model.init(KEY)
    b = 2
    cache = model.init_decode_state(b, 64)
    if cfg.family == "audio":
        frames = jax.random.normal(KEY, (b, cfg.encoder_seq_len, cfg.d_model))
        cache = model.prefill_encoder(params, cache, frames)
    step = jax.jit(model.decode_step)
    tok = jnp.zeros((b, 1), jnp.int32)
    for pos in range(3):
        cache, logits = step(params, cache, tok, jnp.int32(pos))
        assert logits.shape == (b, 1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits))), arch


@pytest.mark.parametrize("arch", ["yi-9b", "deepseek-v2-236b",
                                  "recurrentgemma-9b", "xlstm-1.3b",
                                  "qwen2.5-14b", "grok-1-314b",
                                  "mistral-large-123b", "smollm-135m"])
def test_decode_matches_forward(arch):
    """Feeding tokens one-by-one through decode_step reproduces the
    teacher-forced forward logits — validates every cache implementation."""
    spec = get_arch(arch)
    cfg = spec.reduced.replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(KEY)
    b, t = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(7), (b, t), 0, cfg.vocab_size)
    fwd = model.logits(params, {"tokens": tokens})          # (b, t, V)

    cache = model.init_decode_state(b, t + 4, dtype_kv=jnp.float32)
    step = jax.jit(model.decode_step)
    for pos in range(t):
        cache, lg = step(params, cache, tokens[:, pos:pos + 1], jnp.int32(pos))
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(fwd[:, pos]),
            atol=2e-3, rtol=2e-3, err_msg=f"{arch} pos={pos}")


def test_sliding_window_decode_matches_forward():
    """Ring-buffer windowed cache == windowed forward (the long_500k path)."""
    cfg = get_arch("yi-9b").reduced.replace(dtype="float32", sliding_window=8)
    model = get_model(cfg)
    params = model.init(KEY)
    b, t = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(3), (b, t), 0, cfg.vocab_size)
    fwd = model.logits(params, {"tokens": tokens})
    cache = model.init_decode_state(b, t, dtype_kv=jnp.float32)
    step = jax.jit(model.decode_step)
    for pos in range(t):
        cache, lg = step(params, cache, tokens[:, pos:pos + 1], jnp.int32(pos))
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(fwd[:, pos]),
            atol=2e-3, rtol=2e-3, err_msg=f"pos={pos}")


def test_chunked_equals_naive_full_model():
    """Whole-model check: chunked vs naive attention paths agree."""
    base = get_arch("yi-9b").reduced.replace(dtype="float32")
    tokens = jax.random.randint(KEY, (2, 64), 0, base.vocab_size)
    m_naive = get_model(base.replace(attn_impl="naive"))
    m_chunk = get_model(base.replace(attn_impl="chunked", chunk_size=16))
    params = m_naive.init(KEY)
    a = m_naive.logits(params, {"tokens": tokens})
    b = m_chunk.logits(params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                               rtol=2e-4)


def test_chunked_equals_naive_xlstm():
    base = get_arch("xlstm-1.3b").reduced.replace(dtype="float32")
    tokens = jax.random.randint(KEY, (2, 64), 0, base.vocab_size)
    m_naive = get_model(base.replace(attn_impl="naive"))
    m_chunk = get_model(base.replace(attn_impl="chunked", chunk_size=16))
    params = m_naive.init(KEY)
    a = m_naive.logits(params, {"tokens": tokens})
    b = m_chunk.logits(params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3,
                               rtol=5e-3)


def test_chunked_equals_naive_recurrentgemma():
    base = get_arch("recurrentgemma-9b").reduced.replace(dtype="float32")
    tokens = jax.random.randint(KEY, (2, 64), 0, base.vocab_size)
    m_naive = get_model(base.replace(attn_impl="naive"))
    m_chunk = get_model(base.replace(attn_impl="chunked", chunk_size=16))
    params = m_naive.init(KEY)
    a = m_naive.logits(params, {"tokens": tokens})
    b = m_chunk.logits(params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                               rtol=2e-4)


def test_moe_router_load_balance_loss_positive():
    cfg = get_arch("grok-1-314b").reduced
    model = get_model(cfg)
    params = model.init(KEY)
    _, aux = model.loss(params, _batch_for(cfg))
    assert float(aux["aux"]) > 0.0


def test_vlm_patch_positions_excluded_from_loss():
    cfg = get_arch("llava-next-mistral-7b").reduced.replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(KEY)
    b = _batch_for(cfg)
    # loss must be computed over text logits only: value should be finite and
    # logits shape covers patches+text
    lg = model.logits(params, b)
    assert lg.shape[1] == cfg.num_image_patches + b["tokens"].shape[1]
    loss, _ = model.loss(params, b)
    assert jnp.isfinite(loss)


def test_scan_and_unrolled_agree():
    """scan-over-layers == unrolled layers for identical params."""
    cfg_s = get_arch("yi-9b").reduced.replace(dtype="float32", num_layers=4,
                                              scan_layers=True)
    cfg_u = cfg_s.replace(scan_layers=False)
    m_s, m_u = get_model(cfg_s), get_model(cfg_u)
    params_s = m_s.init(KEY)
    # restack scanned params into the unrolled layout
    layers = [jax.tree.map(lambda x: x[i], params_s["groups"])["u0"]
              for i in range(4)]
    params_u = {k: v for k, v in params_s.items() if k != "groups"}
    params_u["layers"] = layers
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg_s.vocab_size)
    a = m_s.logits(params_s, {"tokens": tokens})
    b = m_u.logits(params_u, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_whisper_decode_matches_forward():
    """Enc-dec: step-by-step decode == teacher-forced decoder forward."""
    cfg = get_arch("whisper-tiny").reduced.replace(dtype="float32")
    model = get_model(cfg)
    params = model.init(KEY)
    b, t = 2, 10
    frames = jax.random.normal(KEY, (b, cfg.encoder_seq_len, cfg.d_model))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (b, t), 0,
                                cfg.vocab_size)
    fwd = model.logits(params, {"frames": frames, "tokens": tokens})
    cache = model.init_decode_state(b, t + 2, dtype_kv=jnp.float32)
    cache = model.prefill_encoder(params, cache, frames)
    step = jax.jit(model.decode_step)
    for pos in range(t):
        cache, lg = step(params, cache, tokens[:, pos:pos + 1], jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(fwd[:, pos]),
                                   atol=2e-3, rtol=2e-3, err_msg=f"pos={pos}")


def test_gshard_moe_equals_ragged_high_capacity():
    import dataclasses
    base = get_arch("deepseek-v2-236b").reduced.replace(dtype="float32")
    cfg_g = base.replace(moe=dataclasses.replace(base.moe, impl="gshard",
                                                 capacity_factor=8.0))
    m_r, m_g = get_model(base), get_model(cfg_g)
    params = m_r.init(KEY)
    tokens = jax.random.randint(KEY, (2, 32), 0, base.vocab_size)
    a = m_r.logits(params, {"tokens": tokens})
    b = m_g.logits(params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                               rtol=2e-4)


def test_gshard_capacity_drop_error_decreases():
    """GShard drops degrade gracefully: error vs the exact path shrinks
    monotonically with capacity_factor and vanishes once no tokens drop."""
    import dataclasses
    base = get_arch("grok-1-314b").reduced.replace(dtype="float32")
    m_r = get_model(base)
    params = m_r.init(KEY)
    tokens = jax.random.randint(KEY, (2, 64), 0, base.vocab_size)
    a = m_r.logits(params, {"tokens": tokens})
    rels = []
    for cf in (1.0, 1.5, 2.5):
        cfg_g = base.replace(moe=dataclasses.replace(base.moe, impl="gshard",
                                                     capacity_factor=cf))
        b = get_model(cfg_g).logits(params, {"tokens": tokens})
        assert bool(jnp.all(jnp.isfinite(b)))
        rels.append(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a)))
    # monotone up to float noise: with no drops all rels sit at ~1e-7
    eps = 1e-6
    assert rels[0] >= rels[1] - eps >= rels[2] - 2 * eps, rels
    assert rels[2] < 1e-4, rels


# -- the packed BMA forward of lenet-radar ----------------------------------

PACKED_HW = (32, 31)                    # odd conv2 output width: a cropped pool


@pytest.mark.parametrize("mode", ["samples", "nodes", "weighted",
                                  "batched_x", "outer_x"])
@pytest.mark.parametrize("members", [1, 3, 8, 12, 16])
def test_lenet_packed_bma_matches_member_loop(members, mode):
    """``bma_predict_stacked`` over ``model.logits`` runs the conv tower
    once for the bank (members packed into the channels, groups of 8, zero
    members padding 3 and 12) and equals a Python loop over the members'
    ``lenet_logits``; with ``x`` batched per member the batching rule falls
    back to exactly the plain per-member vmap, and under an outer vmap over
    inputs each input's members are still packed."""
    from repro.core.posterior import bma_predict_stacked
    from repro.models.lenet import lenet_logits
    model = get_model(get_arch("lenet-radar").reduced.replace(
        input_hw=PACKED_HW))
    ps = [model.init(jax.random.fold_in(KEY, i)) for i in range(members)]
    flat = jax.tree.map(lambda *xs: jnp.stack(xs), *ps)
    x = jax.random.normal(jax.random.fold_in(KEY, 99),
                          (5,) + PACKED_HW + (1,))
    apply = lambda p, b: model.logits(p, b)
    if mode == "batched_x":
        xs = x[None] + 0.1 * jnp.arange(members)[:, None, None, None, None]
        got = jax.vmap(lambda p, xx: model.logits(p, {"x": xx}))(flat, xs)
        want = jax.vmap(lenet_logits)(flat, xs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    if mode == "outer_x":
        xs = jnp.stack([x, -x])
        with jax.default_matmul_precision("highest"):
            got = jax.vmap(lambda xx: jax.vmap(
                lambda p: model.logits(p, {"x": xx}))(flat))(xs)
            want = np.stack([[lenet_logits(p, xx) for p in ps] for xx in xs])
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)
        return
    k = 4 if members % 4 == 0 else 1
    nodes = jax.tree.map(lambda a: a.reshape((members // k, k) + a.shape[1:]),
                         flat)
    w = 1.0 + jnp.arange(members // k, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        member_probs = np.stack([np.asarray(jax.nn.softmax(
            lenet_logits(p, x), axis=-1)) for p in ps])
        if mode == "samples":
            got = bma_predict_stacked(apply, flat, {"x": x})
            want = member_probs.mean(0)
        elif mode == "nodes":
            got = bma_predict_stacked(apply, nodes, {"x": x}, node_axis=1)
            want = member_probs.mean(0)
        else:
            got = bma_predict_stacked(apply, nodes, {"x": x}, node_axis=1,
                                      weights=w)
            per_sample = member_probs.reshape((members // k, k, 5, -1))
            want = np.einsum("s,s...->...", np.asarray(w / w.sum()),
                             per_sample.mean(1))
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)
