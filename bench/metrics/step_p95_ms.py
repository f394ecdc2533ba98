"""step_p95_ms: the 95th percentile of the device time of one model step,
over every execution of the prefill and decode programs in the traced
window. A stall or a slow shape that a rate dilutes shows here."""
import numpy as np


def read(ctx):
    steps = (ctx.trace.programs.get("jit_prefill", [])
             + ctx.trace.programs.get("jit_decode", []))
    if not steps:
        return None
    return float(np.percentile(np.asarray(steps) * 1e3, 95))
