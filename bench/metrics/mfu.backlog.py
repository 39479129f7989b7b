"""Model int8-op utilisation of the chip in a backlog cell (per cent):
the operations of the segments each answered image went through (padded
slots not counted), per second of the window, over the int8 peak."""
import workcount


def read(ctx):
    if ctx.traffic['mode'] != 'backlog' or ctx.peaks is None:
        return None
    per_seg = [workcount.segment_ops(ctx.cfg, seg)
               for seg in range(workcount.n_segments(ctx.cfg))]
    ops = sum(n * sum(per_seg[:workcount.segments_of_answer(ctx.cfg, s)])
              for s, n in ctx.exit_mix.items())
    return 100.0 * ops / ctx.window_s / ctx.peaks['int8_ops_per_s']
