"""moe_prefill_expert_roofline: the expert kernel's share of its roofline in
the prefill program (``jit_prefill``), bounded by operations at these
shapes (``bench/metrics/_experts.py``)."""
from bench.metrics._experts import expert_roofline


def read(ctx):
    return expert_roofline(ctx, "jit_prefill", "prefill")
