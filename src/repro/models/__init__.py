"""Model zoo: factory dispatching on config family."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.models.transformer import make_model as _make_decoder
from repro.models.whisper import make_whisper
from repro.models import lenet as _lenet


def _make_lenet(cfg) -> SimpleNamespace:
    def init(key):
        return _lenet.init_lenet(key, cfg)

    def loss(params, batch, key=None):
        return _lenet.lenet_loss(params, batch, key)

    def logits(params, batch):
        # forward only (a custom_vmap has no reverse mode): differentiate
        # ``loss``, which calls the plain forward
        return _lenet.lenet_predict(params, batch["x"])

    return SimpleNamespace(cfg=cfg, init=init, loss=loss, logits=logits,
                           init_decode_state=None, decode_step=None)


def get_model(cfg) -> SimpleNamespace:
    if cfg.family == "lenet":
        return _make_lenet(cfg)
    if cfg.family == "audio":
        return make_whisper(cfg)
    return _make_decoder(cfg)
