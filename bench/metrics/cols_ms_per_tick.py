"""cols_ms_per_tick: device time of the column update, per simulated tick.

Self time of the ops under the program's named scope `tick/cols`: the column
pass behind its `lax.cond`, the column kernel and its wrapper's lane padding,
the gathers and scatters of the batched column path, the Zj bump; averaged over
the chips, per traced tick. None where the trace has no scope map (a program
without the scopes)."""
import scopes


def read(ctx):
    return scopes.phase_ms_per_tick(ctx, "cols")
