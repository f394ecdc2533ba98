"""decode_layer_scan_share: the share of the decode program's (``jit_decode``)
device op time whose innermost program scope is ``layers``: the layer
scan's own slicing of its stacked weights and cache and the stacking of
its outputs, outside any block."""
from bench.metrics._scopes import decode_scope_share


def read(ctx):
    return decode_scope_share("layers")
