"""wta_ms_per_tick: device time of the WTA, per simulated tick.

Self time of the ops under the program's named scope `tick/wta`: the per-HCU
keys, support integration and soft WTA, the fired-batch compaction and the
fire-drop count; averaged over the chips, per traced tick. None where the trace
has no scope map (a program without the scopes)."""
import scopes


def read(ctx):
    return scopes.phase_ms_per_tick(ctx, "wta")
