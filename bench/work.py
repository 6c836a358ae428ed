"""Required operations and bytes of a BCPNN tick, counted from useful work.

The counts follow from what the model must compute, not from how the system
lays it out: a touched synaptic row is C cells, a fired column is R cells,
whatever padding, tiling or grid a kernel adds. A kernel that skips padding
therefore reads a higher share of its roofline, and the same work is
counted whatever implements it.

Per synaptic cell brought current (a row update or a column update):
  bytes  read Z, E, P and the last-update tick T (4 x 4 B); write Z, E, P,
         W and T (5 x 4 B)                                      -> 36 B
  ops    elapsed time 1, three exponentials with their scaling 6, E 4, P 11,
         Z with its increment 3, the weight log((P+e^2)/((Pi+e)(Pj+e))) 6
                                                                -> 31
Per touched row, its presynaptic i-vector entry: read and write Z, E, P, T
(32 B) and its decay (25 ops). Per fired column, the presynaptic traces of
all R rows are brought current as values (read 16 B, 25 ops per row); the
column kernel itself receives two of them per row (8 B).
Per HCU and tick: the j-vector decay and the support (read and write Zj,
Ej, Pj, h: 32 B per column; 30 ops per column), and the consumed delay
bucket plus the external rows (4 B per slot).
Per fan-out message: one queue entry written (4 B) and its count (4 B).

Every operation is counted as one, transcendentals included; a cell's
operations are elementwise and bytes bound it on any chip in `peaks.json`,
so a share of the roofline is a share of HBM bandwidth here.
"""
from __future__ import annotations

CELL_BYTES = 36
CELL_OPS = 31
IVEC_BYTES = 32
IVEC_OPS = 25
PRESYN_BYTES = 16
PRESYN_OPS = 25
COLK_PRESYN_BYTES = 8
JVEC_BYTES = 32
JVEC_OPS = 30
SLOT_BYTES = 4
MSG_BYTES = 8


def row_work(n_rows: int, cols: int):
    """(ops, bytes) of `n_rows` valid row updates: cells and i-vectors."""
    return (n_rows * (cols * CELL_OPS + IVEC_OPS),
            n_rows * (cols * CELL_BYTES + IVEC_BYTES))


def col_kernel_work(n_fired: int, rows: int):
    """(ops, bytes) the column kernel must do for `n_fired` fired columns:
    the cells plus the two presynaptic values it is handed per row."""
    return (n_fired * rows * CELL_OPS,
            n_fired * rows * (CELL_BYTES + COLK_PRESYN_BYTES))


def col_work(n_fired: int, rows: int):
    """(ops, bytes) of a tick's column phase: cells plus bringing the
    presynaptic traces of every row current."""
    return (n_fired * rows * (CELL_OPS + PRESYN_OPS),
            n_fired * rows * (CELL_BYTES + PRESYN_BYTES))


def tick_work(n_ticks: int, n_hcu: int, cols: int, rows: int, slots: int,
              fanout: int, n_rows: int, n_fired: int):
    """(ops, bytes) of `n_ticks` whole ticks with `n_rows` valid row
    updates and `n_fired` fired columns among them."""
    ro, rb = row_work(n_rows, cols)
    co, cb = col_work(n_fired, rows)
    per_tick_o = n_hcu * cols * JVEC_OPS
    per_tick_b = n_hcu * (cols * JVEC_BYTES + slots * SLOT_BYTES)
    return (ro + co + n_ticks * per_tick_o,
            rb + cb + n_ticks * per_tick_b + n_fired * fanout * MSG_BYTES)


def roofline_s(ops: float, nbytes: float, peak: dict):
    """(least seconds the chip needs, the bound: "bytes" or "ops")."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
