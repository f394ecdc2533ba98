"""moe_decode_dispatch_share: the share of the decode program's
(``jit_decode``) device op time whose innermost program scope is
``router`` (router product, softmax, top-k) or ``dispatch`` (the sort by
expert, the gather of rows, their un-sorting and the gated sum): the
routed-expert layer's work outside its grouped products."""
from bench.metrics._scopes import SCOPES, decode_scope_share


def read(ctx):
    if "router" not in SCOPES or "dispatch" not in SCOPES:
        return None
    router, dispatch = (decode_scope_share(s) for s in ("router", "dispatch"))
    if router is None or dispatch is None:
        return None
    return router + dispatch
