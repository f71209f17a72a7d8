"""Faults planted under the timed path, to show that the check catches them.

Used by ``bench/tests`` on the CPU and by ``bench/control.py`` on the chip;
the benchmark's own runs never plant one.

* ``unchanged``: every round returns the node state it was given;
* ``half_batch``: every local step sees the first half of its minibatch
  only, and the mean is taken over that half;
* ``altered_answer``: the first slot of every serving step answers with its
  class probabilities rotated by one class;
* ``half_bank``: the BMA averages over the first half of the bank's samples
  only.
"""
from __future__ import annotations

from contextlib import contextmanager

TRAIN_FAULTS = ("unchanged", "half_batch")
SERVE_FAULTS = ("altered_answer", "half_bank")


@contextmanager
def plant(fault: str):
    import jax
    import jax.numpy as jnp
    import repro.core as core
    from repro.core import posterior

    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault in TRAIN_FAULTS:
        real = core.make_round_fn

        def make_round_fn(*a, **kw):
            rf = real(*a, **kw)

            def unchanged(state, batches, key):
                new, metrics = rf(state, batches, key)
                return state._replace(round=new.round), metrics

            def half_batch(state, batches, key):
                half = jax.tree.map(lambda b: b[:, :, :b.shape[2] // 2],
                                    batches)
                return rf(state, half, key)
            return {"unchanged": unchanged, "half_batch": half_batch}[fault]
        patch(core, "make_round_fn", make_round_fn)
    elif fault == "half_bank":
        real_bma = posterior.bma_predict_stacked

        def bma_predict_stacked(apply_fn, stacked, batch, **kw):
            half = jax.tree.map(lambda a: a[:a.shape[0] // 2], stacked)
            return real_bma(apply_fn, half, batch, **kw)
        patch(posterior, "bma_predict_stacked", bma_predict_stacked)
    elif fault == "altered_answer":
        real_predict = posterior.BankPredictor.predict

        def predict(self, batch):
            probs, ent = real_predict(self, batch)
            return probs.at[0].set(jnp.roll(probs[0], 1)), ent
        patch(posterior.BankPredictor, "predict", predict)
    else:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)
