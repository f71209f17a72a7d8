"""Device ms per round of the transformer's attention: every block's mixer
(QKV products, rotary positions, the chunked causal softmax, the output
product), forward, recomputation and backward, read as the ops under the
model scope ``attention`` inside the local steps (``bench/lm_scopes.py``)."""
from bench import lm_scopes


def read(ctx):
    return lm_scopes.scope_ms_per_round(ctx, "attention")
