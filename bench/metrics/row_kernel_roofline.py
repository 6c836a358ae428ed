"""row_kernel_roofline: the row kernel's share of its roofline.

Roofline time of the row work the traced ticks needed (`work.row_work` on
the valid, deduplicated touched rows) over the summed device time, on every
chip, of the Pallas row kernels (`fused_row_update_kernel_call` on the
worklist path; any op named `*row_update_kernel_call*`). None when none ran."""
import work
import xtrace

PART = "row_update_kernel_call"


def read(ctx):
    t = sum(xtrace.time_containing(ev, PART)
            for ev in ctx["trace"].devices.values())
    if t <= 0:
        return None
    ops, nbytes = work.row_work(ctx["n_rows"], ctx["m"].cols)
    least, _ = work.roofline_s(ops, nbytes, ctx["peak"])
    return 100.0 * least / (t / 1e9)
