"""exchange_exposed_ms_per_tick: collective time not hidden by other ops.

Per chip, the time in which a collective op (the spike all-to-all) runs and
no other op does; the worst chip, per simulated tick. None when the trace
holds no collective."""
import xtrace


def read(ctx):
    devs = ctx["trace"].devices
    if ctx["ticks"] <= 0 or not any(
            xtrace.COLLECTIVE.match(n) for ev in devs.values()
            for n, _, _ in ev):
        return None
    worst = max(xtrace.exposed_collective_ns(ev) for ev in devs.values())
    return worst / 1e6 / ctx["ticks"]
