"""moe_decode_expert_roofline: the expert kernel's share of its roofline in
the decode step program (``jit_decode``), bounded by the bytes of the
experts that got a row at these shapes (``bench/metrics/_experts.py``)."""
from bench.metrics._experts import expert_roofline


def read(ctx):
    return expert_roofline(ctx, "jit_decode", "decode")
