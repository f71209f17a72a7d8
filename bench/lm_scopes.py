"""Device time of the transformer's own scopes inside the CD-BFL round.

The program's training loss runs every block's mixer under the named scope
``attention`` and the final norm, tied-head product, log-sum-exp and target
gather under ``xent``; both sit inside the round scope ``local_step``. The
compiled chunk program's text gives each op its share of the round scopes
and of these two (``bench/scopes.py``'s ``op_shares``, the innermost scope
on an op's path winning), and the trace gives each op's device seconds.
A program without these scopes gives no reading.
"""
from __future__ import annotations

from bench import scopes

LM_SCOPES = ("attention", "xent")


def chunk_text(cfg: dict, traffic: dict) -> str | None:
    """The optimized HLO text of the training cell's chunk program, compiled
    as ``scopes.chunk_shares`` compiles it (the op metadata in the cache
    key); None where the program offers no ``lower_chunk``."""
    import gc

    import jax

    from bench import train
    from repro.train.engine import ScanRoundEngine
    if not hasattr(ScanRoundEngine, "lower_chunk"):
        return None
    engine, state, key, bank = train.build(cfg, traffic, seed=0)
    lowered = engine.lower_chunk(state, key, bank, int(traffic["chunk"]))
    del engine, state, key, bank
    gc.collect()
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          before)


def lm_shares(ctx: dict):
    """``op_shares`` over the round scopes and ``LM_SCOPES``, built once per
    run and kept in ``ctx``; None where there is no chunk program."""
    if "lm_shares" not in ctx:
        text = chunk_text(ctx["config"], ctx["traffic"])
        ctx["lm_shares"] = (None if text is None else scopes.op_shares(
            text, scopes.ROUND_SCOPES + LM_SCOPES))
    return ctx["lm_shares"]


def scope_seconds(ctx: dict, scope: str) -> float | None:
    """Device seconds in the traced window under ``scope``; None where the
    trace has nothing or no op of the program carries the scope."""
    t, c = ctx["trace"], ctx["counts"]
    if not t or not t["op_time"] or not c.get("rounds"):
        return None
    shares = lm_shares(ctx)
    if not shares or not any(scope in s for s in shares.values()):
        return None
    return sum(sec * shares.get(name, {}).get(scope, 0.0)
               for name, sec in t["op_time"].items())


def scope_ms_per_round(ctx: dict, scope: str) -> float | None:
    sec = scope_seconds(ctx, scope)
    return None if sec is None else 1e3 * sec / ctx["counts"]["rounds"]
