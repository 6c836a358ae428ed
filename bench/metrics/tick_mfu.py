"""tick_mfu: the whole tick's share of the chip's peak.

The least time the chips need for the traced ticks' required work
(`work.tick_work`: the valid row updates, the fired columns, the j-vectors,
the queues), the larger of operations over peak FLOP/s and bytes over peak
HBM bandwidth, split over the cell's chips, as a share of the traced
window's wall time. Bytes bound it (see work.py)."""
import work


def read(ctx):
    m = ctx["m"]
    if ctx["ticks"] <= 0 or ctx["window_s"] <= 0:
        return None
    ops, nbytes = work.tick_work(ctx["ticks"], m.n_hcu, m.cols, m.rows,
                                 ctx["slots"], m.fanout, ctx["n_rows"],
                                 ctx["n_fired"])
    least, _ = work.roofline_s(ops / ctx["chips"], nbytes / ctx["chips"],
                               ctx["peak"])
    return 100.0 * least / ctx["window_s"]
