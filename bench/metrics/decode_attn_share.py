"""decode_attn_share: the share of the decode program's (``jit_decode``)
device op time whose innermost program scope is ``attn_cache``, attention
over the cached keys and values (scores, softmax and the weighted sum).
``None`` for a program whose scopes do not name that part."""
from bench.metrics._scopes import SCOPES, decode_scope_share


def read(ctx):
    if "attn_cache" not in SCOPES:
        return None
    return decode_scope_share("attn_cache")
