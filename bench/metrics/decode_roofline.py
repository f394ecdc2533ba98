"""decode_roofline: the decode step program's (``jit_decode``) share of
its roofline, bounded by HBM bytes at these shapes: every weight, the
cache read up to each step's position and the new position written."""
from bench.metrics._roofline import roofline_share


def read(ctx):
    return roofline_share(ctx, "jit_decode", "decode")
