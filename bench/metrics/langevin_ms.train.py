"""Device ms per round of the Langevin step (paper Eq. 9): the noise draw
and the params update, read as the ops under the round scope
``langevin_noise``, with their share of the fusions they join
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.train_scope_ms(ctx, ('langevin_noise',))
