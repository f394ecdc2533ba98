"""A step program's share of its roofline, from the device trace.

The least time the chip could take for the operations and bytes of every
execution of the program in the window (``bench/flops.py``), the larger
of operations over peak FLOP/s and bytes over peak HBM bandwidth, over
the device time of those executions in the trace. Nothing to read, and
``None``, where the trace holds no execution or holds another count of
them than the window ran.
"""


def roofline_share(ctx, program: str, step: str):
    durations = ctx.trace.programs.get(program, [])
    w = ctx.work[step]
    if not durations or len(durations) != w["calls"]:
        return None
    floor = max(w["flops"] / (ctx.peaks["bf16_flops_per_s"] * ctx.chips),
                w["bytes"] / (ctx.peaks["hbm_bytes_per_s"] * ctx.chips))
    return 100.0 * floor / sum(durations)
