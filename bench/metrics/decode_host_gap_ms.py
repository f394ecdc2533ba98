"""decode_host_gap_ms: device idle time per decode step while the host is
in the engine's ``serve.sample``, ``serve.schedule`` or ``serve.decode``
span (the gap's midpoint, after the device clock is aligned to the
host's), over the ``jit_decode`` executions in the window."""
from bench.metrics._scopes import decode_host_gap_ms


def read(ctx):
    return decode_host_gap_ms()
