"""prefill_roofline: the prefill program's (``jit_prefill``) share of its
roofline, bounded by operations at these shapes."""
from bench.metrics._roofline import roofline_share


def read(ctx):
    return roofline_share(ctx, "jit_prefill", "prefill")
