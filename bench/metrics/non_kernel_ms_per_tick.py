"""non_kernel_ms_per_tick: device time outside the Pallas kernels.

Per chip: the union of device op intervals minus the time of every Pallas
kernel (ops named `*_kernel_call*`: the row and the column kernel),
averaged over the chips, per simulated tick. The kernel wrappers' lane-pad
copies and the rest of the tick's XLA ops land here."""
import xtrace


def read(ctx):
    devs = ctx["trace"].devices
    if not devs or ctx["ticks"] <= 0:
        return None
    ns = [xtrace.busy_ns(ev) - xtrace.time_containing(ev, "_kernel_call")
          for ev in devs.values()]
    return sum(ns) / len(ns) / 1e6 / ctx["ticks"]
