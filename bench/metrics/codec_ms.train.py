"""Device ms per round of the codec (paper Eq. 6): Q of each node's residual
into the packed payload and its decode, read as the ops under the round
scopes ``encode`` and ``decode``, with the layout copies and loops the
compiler adds around the pack and unpack kernels (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.train_scope_ms(ctx, ('encode', 'decode'))
