"""device_idle_share.serve: the share of the traced window in which no
operation ran on the device (1 - union of op intervals / window),
averaged over the cell's chips."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
