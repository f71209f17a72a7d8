"""Device ms per round of the training loss's cross-entropy over the tied
head: final norm, the head product, log-sum-exp and the target gather of
every chunk of tokens, forward, recomputation and backward, read as the ops
under the model scope ``xent`` (``bench/lm_scopes.py``)."""
from bench import lm_scopes


def read(ctx):
    return lm_scopes.scope_ms_per_round(ctx, "xent")
