"""lane_pad_ms_per_tick: device time of the kernel wrappers' lane padding.

Self time of the ops under the named scope `lane_pad` that every Pallas
kernel wrapper in `kernels/ops.py` opens around padding the planes to 128
lanes (and the operands to whole tiles) and slicing the results back to C
columns; it sits under `tick/rows` or `tick/cols`, wherever the wrapper is
called, so it is part of those phases' time. Averaged over the chips, per
traced tick; None where the trace has no scope map."""
import scopes


def read(ctx):
    return scopes.phase_ms_per_tick(ctx, scopes.LANE_PAD)
