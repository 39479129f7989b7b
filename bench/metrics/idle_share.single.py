"""Share of the traced window in which no operation ran on the chip, in
the one-client closed-loop cell (per cent).  From the profiler trace:
1 - busy / window."""


def read(ctx):
    if ctx.traffic['mode'] != 'closed' or ctx.trace is None:
        return None
    return 100.0 * ctx.trace['idle_share']
