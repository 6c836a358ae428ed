"""The one traffic generator: external spike rows from a mix's data file.

A mix (`bench/traffic/<name>.json`) holds only parameters:

    width          slots of external input per HCU per tick (rows past the
                   Poisson draw are padding, row index R); 0 when the
                   cell's own fan-out brings every arrival
    buffer_ticks   ticks of input staged on the device at set-up; the
                   measured window cycles through them
    rate_schedule  [[ticks, rate], ...]: mean arrivals per ms per HCU in
                   all, piecewise constant, repeated over the buffer

An HCU's arrivals are its share of the network's recurrent spikes plus the
external drive. The harness keeps every fan-out target of a cell on its
chips, so the recurrent spikes bring out_rate x fanout rows per ms to each
HCU, and the external drive brings the rest: Poisson(rate - out_rate x
fanout) rows per tick, each uniform over the HCU's R rows. Draws past
`width` are clipped. Everything is drawn from the run's seed with NumPy in
bulk.
"""
from __future__ import annotations

import numpy as np

SALT = 0x7A1


def rates(mix: dict, recurrent: float = 0.0) -> np.ndarray:
    """Mean external rows per ms per HCU for each of the buffer's ticks:
    the schedule's arrivals less the `recurrent` ones, never below 0."""
    sched = [r for n, r in mix["rate_schedule"] for _ in range(int(n))]
    if not sched:
        raise ValueError("rate_schedule is empty")
    T = int(mix["buffer_ticks"])
    lam = np.resize(np.asarray(sched, np.float64), T) - recurrent
    return np.maximum(lam, 0.0)


def external_rows(mix: dict, n_hcu: int, rows: int, seed: int,
                  recurrent: float = 0.0) -> np.ndarray:
    """(buffer_ticks, n_hcu, width) int32 external rows, padding == rows."""
    lam = rates(mix, recurrent)
    T, W = lam.shape[0], int(mix["width"])
    if W == 0 and lam.max() > 0:
        raise ValueError(f"an external drive of up to {lam.max()} rows/ms "
                         f"needs a width above 0")
    rng = np.random.default_rng([seed, SALT])
    counts = np.minimum(rng.poisson(lam[:, None], (T, n_hcu)), W)
    out = rng.integers(0, rows, (T, n_hcu, W), dtype=np.int32)
    out[np.arange(W)[None, None, :] >= counts[:, :, None]] = rows
    return out
