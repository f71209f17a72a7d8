"""Device ms per round of the posterior bank's admission of the round's
params, read as the ops under the scope ``bank_admit``
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.train_scope_ms(ctx, ('bank_admit',))
