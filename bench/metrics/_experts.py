"""The expert kernel's share of its roofline in one step program, from the
device trace: device time of the operations whose innermost program scope
is ``experts`` (the grouped products of ``models/moe.moe_sparse``, and the
SwiGLU between them), against the least time the chip could take for the
expert operations and bytes that the routing's counters give
(``bench/flops_moe.expert_flops`` / ``expert_bytes``: only the experts that
got a row are read). ``None`` where the program sets no ``experts`` scope,
the run carries no counters, or the trace holds another count of the
program's executions than the window ran."""
from bench.metrics._scopes import SCOPES, window_summary


def expert_roofline(ctx, program: str, step: str):
    w = ctx.work.get(f"experts_{step}")
    if "experts" not in SCOPES or w is None:
        return None
    s = window_summary()
    seconds = s.op_s.get((program, "experts"), 0.0)
    if seconds <= 0 or s.executions.get(program) != w["calls"]:
        return None
    floor = max(w["flops"] / (ctx.peaks["bf16_flops_per_s"] * ctx.chips),
                w["bytes"] / (ctx.peaks["hbm_bytes_per_s"] * ctx.chips))
    return 100.0 * floor / seconds
