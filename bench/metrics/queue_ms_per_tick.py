"""queue_ms_per_tick: device time of the delay queues, per simulated tick.

Self time of the ops under the program's named scope `tick/queue`: consuming
the tick's delay bucket and merging the external rows, the fan-out gathers from
the connectivity, and the enqueue of the fired spikes (or the exchange's send
and receive in a sharded tick); averaged over the chips, per traced tick. None
where the trace has no scope map (a program without the scopes)."""
import scopes


def read(ctx):
    return scopes.phase_ms_per_tick(ctx, "queue")
