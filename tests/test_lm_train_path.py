"""The transformer's training loss on the reduced SmolLM: layer
recomputation and the chunked cross-entropy over the tied head give the
loss and gradients of the full-logits loss; the benchmark's plain reference
gives the same NLL."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.models import get_model

# float32 products; 16-token chunks, so that 2 x 72 tokens span nine
# cross-entropy chunks, the last one padded
CFG = get_arch("smollm-135m").reduced.replace(dtype="float32", chunk_size=16)
B, S = 2, 72


def _batch(mask: bool):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    batch = {"tokens": jax.random.randint(k1, (B, S), 0, CFG.vocab_size)}
    if mask:
        batch["loss_mask"] = (jax.random.uniform(k2, (B, S)) > 0.3).astype(
            jnp.int32)
    return batch


def _full_logits_loss(model, params, batch):
    """The loss as the whole ``(B, S, V)`` float32 logits and their
    log-softmax give it, with no recomputation."""
    lg = model.logits(params, batch)
    logp = jax.nn.log_softmax(lg[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["tokens"][:, 1:, None], -1)[..., 0]
    if "loss_mask" not in batch:
        return jnp.mean(nll)
    m = batch["loss_mask"][:, 1:].astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


@pytest.mark.parametrize("mask", [False, True], ids=["all", "loss_mask"])
def test_chunked_recomputed_loss_equals_full_logits(mask):
    model = get_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(mask)
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch)[0]))(params)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: _full_logits_loss(model, params=p, batch=batch)))(params)
    # the same float32 sums in another order (log-sum-exp against
    # log-softmax, chunk by chunk): a few ulps of a loss near 6
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_got)[0],
                            jax.tree.leaves(g_want)):
        # the embedding's gradient sums over chunks and tokens in another
        # order; 1e-5 of the leaf's largest entry is float32 rounding of
        # sums of a few hundred terms
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=1e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=jax.tree_util.keystr(path))


def test_training_path_traces_the_chunked_loss():
    model = get_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    assert model.chunked_xent_traces == 0
    jax.jit(jax.grad(lambda p: model.loss(p, _batch(False))[0]))(params)
    assert model.chunked_xent_traces == 1
    # inference takes the whole head, as before
    model.logits(params, _batch(False))
    assert model.chunked_xent_traces == 1


def test_training_path_names_attention_and_xent():
    model = get_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    text = jax.jit(jax.grad(lambda p: model.loss(p, _batch(False))[0])
                   ).lower(params).as_text(debug_info=True)
    assert "attention" in text and "xent" in text


def test_reference_nll_equals_the_program():
    from bench.reference import smollm as ref
    cfg = {"num_layers": CFG.num_layers, "d_model": CFG.d_model,
           "num_heads": CFG.num_heads, "num_kv_heads": CFG.num_kv_heads,
           "d_ff": CFG.d_ff, "vocab_size": CFG.vocab_size,
           "norm_eps": CFG.norm_eps, "rope_theta": CFG.rope_theta}
    model = get_model(CFG)
    params = model.init(jax.random.PRNGKey(1))
    batch = _batch(False)
    got = float(jax.jit(lambda p: model.loss(p, batch)[0])(params))
    want = float(jax.jit(lambda p: ref.nll_for(cfg)(p, batch))(params))
    # two float32 programs written apart (chunked log-sum-exp against a
    # per-sequence log-softmax, the program's GQA reshape against repeated
    # key heads): rounding alone
    np.testing.assert_allclose(got, want, rtol=1e-5)
