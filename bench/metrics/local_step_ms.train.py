"""Device ms per round of the local steps (paper Eq. 5): the L SGLD steps
of every node, forward, backward and update, read as the ops under the
round scope ``local_step`` (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.train_scope_ms(ctx, ('local_step',))
