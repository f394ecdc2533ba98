"""decode_unscoped_share: the share of the decode program's (``jit_decode``)
device op time under no program scope, such as the copies XLA adds
itself: how much of the step the tracing cannot name."""
from bench.metrics._scopes import NO_SCOPE, decode_scope_share


def read(ctx):
    return decode_scope_share(NO_SCOPE)
