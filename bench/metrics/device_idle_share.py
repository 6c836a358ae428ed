"""device_idle_share: the share of the traced window in which no op ran,
1 - (union of device op intervals) / window, averaged over the chips."""
import xtrace


def read(ctx):
    devs = ctx["trace"].devices
    if not devs or ctx["window_s"] <= 0:
        return None
    busy = sum(xtrace.busy_ns(ev) for ev in devs.values()) / len(devs) / 1e9
    return 100.0 * (1.0 - busy / ctx["window_s"])
