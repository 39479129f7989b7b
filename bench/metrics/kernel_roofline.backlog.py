"""The conv and fc work's share of its roofline in a backlog cell
(per cent): the least time of every segment batch the window executed,
over the chip's busy time in the window.

The least time of a batch sums, over the segment's convs and fcs at their
published shapes, the larger of int8 ops / int8 peak and bytes / HBM
bandwidth, for the whole slot geometry as launched (bench/workcount.py).
The batches are the scheduler's own count; busy time is from the trace.
Whatever else the chip runs (the im2col gather, norms, padding, copies)
counts in busy time and not in the least time, so it lowers the share."""
import workcount


def read(ctx):
    if (ctx.traffic['mode'] != 'backlog' or ctx.trace is None
            or ctx.peaks is None or not ctx.trace['busy_s']):
        return None
    least = sum(n * workcount.segment_least_s(ctx.cfg, seg, ctx.slots,
                                              ctx.peaks)
                for seg, n in enumerate(ctx.segment_batches))
    return 100.0 * least / ctx.trace['busy_s']
