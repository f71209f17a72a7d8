"""Device ms per round of the mix (paper Eqs. 7-8): the gossip of the
decoded deltas and the v and v-bar updates, read as the ops under the
round scope ``mix``, with the layout loops of its ring shift
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.train_scope_ms(ctx, ('mix',))
