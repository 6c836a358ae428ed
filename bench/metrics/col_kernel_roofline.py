"""col_kernel_roofline: the fused column megakernel's share of its roofline.

Roofline time of the column work the traced ticks needed
(`work.col_kernel_work` on the fired columns of the fired batch, R cells
each) over the summed device time, on every chip, of
`fused_col_update_kernel_call`, the megakernel that does the whole column
update for a fired batch of any size (a batch over one lane tile reads
its presynaptic traces a tile at a time). None where no such op ran."""
import work
import xtrace

KERNEL = "fused_col_update_kernel_call"


def read(ctx):
    t = sum(xtrace.time_of(ev, KERNEL)
            for ev in ctx["trace"].devices.values())
    if t <= 0:
        return None
    ops, nbytes = work.col_kernel_work(ctx["n_fired"], ctx["m"].rows)
    least, _ = work.roofline_s(ops, nbytes, ctx["peak"])
    return 100.0 * least / (t / 1e9)
