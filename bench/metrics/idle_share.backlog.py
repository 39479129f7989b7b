"""Share of the traced window in which no operation ran on the chip, in a
backlog cell (per cent).  From the profiler trace: 1 - busy / window,
busy being the union of the chip's op intervals inside the window."""


def read(ctx):
    if ctx.traffic['mode'] != 'backlog' or ctx.trace is None:
        return None
    return 100.0 * ctx.trace['idle_share']
