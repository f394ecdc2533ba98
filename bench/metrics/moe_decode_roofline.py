"""moe_decode_roofline: the decode step program's (``jit_decode``) share of
its roofline, as ``decode_roofline`` reads it, with the bytes of an MoE
step (``bench/flops_moe.py``): the weights outside the experts, the
experts that the routing's counters report as having got a row, the cache
read up to each step's position and the new position written. ``None``
where the run carries no counters."""
from bench.metrics._roofline import roofline_share


def read(ctx):
    if "decode" not in ctx.work:
        return None
    return roofline_share(ctx, "jit_decode", "decode")
