"""Seconds the harness spends building or loading, and warming, the cell's
programs: every segment program at the slot geometry, the exit threshold
over the pool, and a warm-up of the cell's own traffic."""


def read(ctx):
    return ctx.compile_s
