"""serve_mfu: the whole serving step's share of the chips' bf16 peak.

Forward operations of every token the window served, counted from shapes
by ``bench/flops.py`` (causal attention's lower triangle, prefill's LM head
at the last position only), over window seconds x chips x peak.
"""


def read(ctx):
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * ctx.work["flops"] / (ctx.window_s * peak)
