"""rows_ms_per_tick: device time of the row update, per simulated tick.

Self time of the ops under the program's named scope `tick/rows`: the row
worklist's dedup and build, the row kernel and its wrapper's lane padding, the
i-vector writeback (the dense backends' per-HCU row update); averaged over the
chips, per traced tick. None where the trace has no scope map (a program
without the scopes)."""
import scopes


def read(ctx):
    return scopes.phase_ms_per_tick(ctx, "rows")
