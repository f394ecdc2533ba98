"""decode_kv_write_share: the share of the decode program's (``jit_decode``)
device op time whose innermost program scope is ``kv_write``, the block's
write of one position into the key/value cache."""
from bench.metrics._scopes import decode_scope_share


def read(ctx):
    return decode_scope_share("kv_write")
