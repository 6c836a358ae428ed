"""Synaptic data organization: Row-Merge tiling and the flat worklist layout.

Two layout concerns live here, both instances of the paper's central theme
(§V.E, §VI.D): the memory layout must make the *touched* synaptic state —
not the whole matrix — the unit of traffic.

1. Row-Merge tiling (paper Fig 9-10), TPU-adapted
-------------------------------------------------
The (R=10000, C=100) synaptic matrix is accessed as rows (per input spike)
AND columns (per output spike). Direct row-major mapping makes a column
access cost one DRAM row-miss per cell. Row-Merge block-interleaves X x X
blocks so a column access hits X cells per DRAM row, minimizing total misses
at X = 10:

    rowmiss(X) = (row_rate * X + col_rate * C/X * C_groups) ...
    paper form: 10000 * (X + 100/X) * 2 per second, min at X = 10.

TPU adaptation: the DRAM row (page) becomes the HBM->VMEM DMA tile. A naive
row-major column access DMAs (8,128) tiles to use 1 lane-column each, i.e.
128x waste in the lane dim. We re-derive the same objective for tiles:

    bytes_touched(Xr, Xc) per second =
        row_rate * ceil(C/Xc) * tile_bytes        (a row crosses C/Xc tiles)
      + col_rate * ceil(R/Xr) * tile_bytes        (a column crosses R/Xr tiles)

and store the matrix as (R/Xr, C/Xc, Xr, Xc) so each tile is contiguous.
With f32 SoA planes the hardware-native tile is (8, 128); because C=100 < 128
a whole logical row fits one tile-row, so the TPU-optimal point degenerates
to Xc = C (pad to 128) and Xr = 8: rows cost 1 tile, columns cost R/8 tiles
— the exact analogue of the paper's conclusion that the layout must serve
BOTH patterns, with the optimum set by the access-rate ratio (100:1).

`benchmarks/fig10_rowmerge.py` sweeps X for the paper's DRAM cost model
(reproducing Fig 10: min at X=10, 5x better than direct) and the TPU tile
model side by side.

2. Flat (H*R, C) canonical layout (paper §VI.D: traffic scales with spikes)
--------------------------------------------------------------------------
The flat layout is the CANONICAL stored form of `NetworkState.hcus`
(`flat_state` below; since the TickEngine refactor): ij planes `(H*R, C)`,
i-vectors `(H*R,)`, j-vectors `(H, C)`. Every touched synaptic row is
addressable by a single global index

    g = h * R + r          (`global_row` below).

Because the layouts are row-major reinterpretations of the same buffer
(`flat_state` / `batched_state` and the per-plane `flatten_plane` /
`unflatten_plane` are reshapes, i.e. bitcasts), per-HCU vmapped code gets
the batched `(H, R, C)` view for free (`network.hcu_view`), checkpoints
persist the flat form (old batched-layout checkpoints migrate through
`checkpoint.restore_network`), and HCU shards stay whole under the
distributed runtime (device d owns flat rows [d*h_local*R, (d+1)*h_local*R)).
What the flat addressing buys is the update
*pattern*: one deduplicated network-wide worklist of global row indices per
tick, consumed by `lax.dynamic_slice`/`dynamic_update_slice` loops (CPU) or
a scalar-prefetch Pallas grid (TPU, `kernels.bcpnn_update.
worklist_update_kernel_call`), both of which rewrite only the touched
`(1, C)` row tiles in place. The per-HCU vmapped gather->update->scatter
forms they replace made XLA materialize a full `(H, R, C)` copy per scatter
on the scan-carried planes — O(planes) traffic per tick, the exact failure
mode the paper's lazy update exists to avoid. A fired column in the flat
view is the `(R, 1)` block at offset `(h*R, j)`, so column updates stay
expressible as single dynamic slices too (`col_offset`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


# ----------------------------- paper's DRAM model ---------------------------

def dram_row_misses_per_s(x: int, rows: int = 10_000, cols: int = 100,
                          row_rate: float = 10_000.0, col_rate: float = 100.0):
    """Paper Fig 10 objective. X must divide `cols`.

    Under Row-Merge with X x X blocks (DRAM row capacity = one `cols`-cell
    matrix row), a row access touches X DRAM rows (its cells are spread over
    the X merged rows of its group) and a column access touches rows/X DRAM
    rows (X column cells co-located per merged row):

        rowmiss(X) = (row_rate * X + col_rate * rows/X) * 2   (read+write)

    At the paper's rates (row_rate=10000/s, col_rate=100/s, R=10000) this is
    their stated closed form 10000 * (X + 100/X) * 2 — min at X = 10, 5.05x
    better than the direct X = 1 mapping (tests/test_layout.py pins both).
    """
    return (row_rate * x + col_rate * (rows / x)) * 2.0


def paper_fig10_table(rows=10_000, cols=100):
    xs = [x for x in range(1, cols + 1) if cols % x == 0]
    return {x: dram_row_misses_per_s(x, rows, cols) for x in xs}


# ----------------------------- TPU tile model -------------------------------

def tile_bytes_touched_per_s(xr: int, xc: int, rows: int, cols: int,
                             row_rate: float, col_rate: float,
                             bytes_per_cell: int = 20):
    """Bytes DMA'd HBM<->VMEM per second under (xr, xc) tiling (read+write)."""
    tile_b = xr * xc * bytes_per_cell
    tiles_per_row = -(-cols // xc)
    tiles_per_col = -(-rows // xr)
    return 2.0 * tile_b * (row_rate * tiles_per_row + col_rate * tiles_per_col)


def best_tile(rows: int, cols: int, row_rate: float, col_rate: float,
              candidates=((8, 128), (8, 256), (16, 128), (32, 128), (8, 512),
                          (64, 128), (128, 128), (256, 128))):
    scored = {c: tile_bytes_touched_per_s(c[0], min(c[1], cols), rows, cols,
                                          row_rate, col_rate)
              for c in candidates}
    best = min(scored, key=scored.get)
    return best, scored


# ----------------------------- layout transform -----------------------------

@dataclasses.dataclass(frozen=True)
class RowMergeLayout:
    """Bijective (R, C) <-> (R/xr, C/xc, xr, xc) tiled layout.

    The tiled form is how synaptic planes are stored in HBM so that both the
    row-update and the column-update Pallas kernels fetch whole contiguous
    tiles (the TPU translation of 'DRAM row == matrix row').
    """
    rows: int
    cols: int
    xr: int = 8
    xc: int = 128

    @property
    def padded_rows(self) -> int:
        return -(-self.rows // self.xr) * self.xr

    @property
    def padded_cols(self) -> int:
        return -(-self.cols // self.xc) * self.xc

    def pack(self, plane: jnp.ndarray) -> jnp.ndarray:
        """(R, C) -> (R'/xr, C'/xc, xr, xc), zero-padded."""
        R, C = plane.shape
        assert (R, C) == (self.rows, self.cols)
        p = jnp.pad(plane, ((0, self.padded_rows - R), (0, self.padded_cols - C)))
        t = p.reshape(self.padded_rows // self.xr, self.xr,
                      self.padded_cols // self.xc, self.xc)
        return t.transpose(0, 2, 1, 3)

    def unpack(self, tiled: jnp.ndarray) -> jnp.ndarray:
        t = tiled.transpose(0, 2, 1, 3).reshape(self.padded_rows,
                                                self.padded_cols)
        return t[: self.rows, : self.cols]

    def row_tiles(self, r: int):
        """Tile coordinates a logical row touches: (tile_r, all tile_cs)."""
        return r // self.xr, np.arange(self.padded_cols // self.xc)

    def col_tiles(self, c: int):
        return np.arange(self.padded_rows // self.xr), c // self.xc


# ----------------------------- flat worklist layout --------------------------

# HCUState fields stored flat (leading axis H*R) in the canonical layout; the
# j-vector/support fields (zj, ej, pj, h) keep their (H, C) shape — they are
# per-HCU dense and always current, so there is nothing to flatten.
_FLAT_PLANE_FIELDS = ("zij", "eij", "pij", "wij", "tij")
_FLAT_VEC_FIELDS = ("zi", "ei", "pi", "ti")


def flat_state(hcus):
    """Batched (H, R, C)/(H, R) HCUState -> the CANONICAL flat layout.

    ij planes become (H*R, C), i-vectors (H*R,); j-vectors stay (H, C).
    Pure reshapes (row-major bitcasts) — values are untouched, so the two
    layouts are bitwise-interchangeable views of the same network.
    """
    upd = {f: flatten_plane(getattr(hcus, f)) for f in _FLAT_PLANE_FIELDS}
    upd.update({f: flatten_vec(getattr(hcus, f)) for f in _FLAT_VEC_FIELDS})
    return hcus._replace(**upd)


def batched_state(hcus, n_hcu: int):
    """Canonical flat HCUState -> the per-HCU batched (H, R, C)/(H, R) view
    that `jax.vmap`-over-HCUs code consumes (zero-copy inverse of
    `flat_state`)."""
    upd = {f: unflatten_plane(getattr(hcus, f), n_hcu)
           for f in _FLAT_PLANE_FIELDS}
    upd.update({f: unflatten_vec(getattr(hcus, f), n_hcu)
                for f in _FLAT_VEC_FIELDS})
    return hcus._replace(**upd)


def flatten_plane(plane: jnp.ndarray) -> jnp.ndarray:
    """(H, R, C) -> (H*R, C) flat view (zero-copy: row-major bitcast)."""
    H, R, C = plane.shape
    return plane.reshape(H * R, C)


def unflatten_plane(flat: jnp.ndarray, n_hcu: int) -> jnp.ndarray:
    """(H*R, C) -> (H, R, C) batched view (zero-copy inverse)."""
    HR, C = flat.shape
    return flat.reshape(n_hcu, HR // n_hcu, C)


def flatten_vec(vec: jnp.ndarray) -> jnp.ndarray:
    """(H, R) i-vector plane -> (H*R,) flat view."""
    H, R = vec.shape
    return vec.reshape(H * R)


def unflatten_vec(flat: jnp.ndarray, n_hcu: int) -> jnp.ndarray:
    return flat.reshape(n_hcu, flat.shape[0] // n_hcu)


def global_row(h, r, rows: int):
    """(hcu, row) -> global flat row index; broadcastable."""
    return h * rows + r


def col_offset(h, j, rows: int):
    """Flat-plane offset of HCU ``h``'s column ``j``: the (R, 1) block at
    (h*R, j) — a fired column is one dynamic slice in the flat view."""
    return h * rows, j


# ----------------------------- pluggable plane layout ------------------------
#
# The PHYSICAL storage order of the ij planes is a pluggable property of the
# canonical state. A PlaneLayout is a frozen hashable value object (usable as
# a jit static argument) with two duties:
#
#   * whole-plane conversion: `store` (canonical flat (H*R, C) -> stored
#     form) and `load` (inverse) — pure f32/int32 data movement, so every
#     layout holds bitwise-identical logical values;
#   * traced accessors for the worklist loops: read/write/stamp of one
#     logical row ((1, C)), one logical column ((R,)), and one cell — the
#     exact seam `repro.core.worklist`'s dynamic-slice loops go through.
#
# Two implementations:
#
#   * FlatLayout — the historical row-major (H*R, C) storage (DEFAULT). Its
#     accessors emit exactly the dynamic-slice expressions the worklist
#     loops always emitted, so flat compute graphs are UNCHANGED by the
#     abstraction (the bitwise-frozen contract of docs/NUMERICS.md).
#   * BlockedLayout — the Row-Merge/column-blocked variant: each HCU's
#     (R, C) plane is stored as (R'/xr, C'/xc, xr, xc) tiles (network-wide:
#     (H*Tr, Tc, xr, xc)), zero-padded to tile multiples. A fired column
#     then touches Tr contiguous (xr, 1)-strided fragments instead of R
#     isolated cells — ~R*xc*4/64 cache lines instead of R (the paper's
#     Fig 9-10 trade re-derived for 64 B lines; `cache_lines_touched_per_s`
#     is the model, `benchmarks/fig10_rowmerge.py` the sweep). At the TPU
#     degenerate point (xr=8, xc=128 >= C) the stored form reshapes to the
#     row-padded flat view the Pallas megakernels already consume, so only
#     index remapping changes (`flat_view`/`pad_row_index`).
#
# Layout is storage order, NOT math: the worklist loop bodies feed the same
# sealed compute islands the same logical (1, C)/(R,) blocks under either
# layout, so trajectories stay fixture-pinned bitwise (the A/B is pinned by
# tests/test_engine_fixtures.py::test_layout_ab).

def cache_lines_touched_per_s(xr: int, xc: int, rows: int, cols: int,
                              row_rate: float, col_rate: float,
                              line_bytes: int = 64, cell_bytes: int = 4):
    """CPU twin of `tile_bytes_touched_per_s`: 64 B cache lines touched per
    second under (xr, xc) blocking (read+write). A logical row touches
    ceil(C/xc) tile-row segments of xc contiguous cells each; a logical
    column touches ceil(R/xr) tiles, min(xr, ceil(xr*xc*cell/line)) lines
    each (within a tile the column's xr cells sit at stride xc*cell). The
    flat layout is the (1, cols) point: ~ceil(C*cell/line) lines per row,
    R lines per column."""
    seg = max(1, -(-(xc * cell_bytes) // line_bytes))
    lines_row = -(-cols // xc) * seg
    per_tile = min(xr, -(-(xr * xc * cell_bytes) // line_bytes))
    lines_col = -(-rows // xr) * per_tile
    return 2.0 * (row_rate * lines_row + col_rate * lines_col)


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """The canonical row-major (H*R, C) storage — the DEFAULT PlaneLayout.

    `layout=None` everywhere means this layout; the class exists so the
    accessor seam has a concrete flat implementation (tests exercise it
    directly). Its methods emit exactly the dynamic-slice expressions the
    worklist loops historically inlined — same primitives, same operands —
    which is what keeps flat graphs bitwise-frozen. ``rows`` is only needed
    by the column/cell accessors (the flat column offset is h*R)."""
    rows: int | None = None

    def store(self, flat: jnp.ndarray) -> jnp.ndarray:
        return flat

    def load(self, stored: jnp.ndarray) -> jnp.ndarray:
        return stored

    def read_row(self, f, g):
        return jax.lax.dynamic_slice(f, (g, 0), (1, f.shape[1]))

    def write_row(self, f, g, val):
        return jax.lax.dynamic_update_slice(f, val, (g, 0))

    def stamp_row(self, f, g, now):
        return jax.lax.dynamic_update_slice(
            f, jnp.full((1, f.shape[1]), now, f.dtype), (g, 0))

    def read_col(self, f, h, j):
        off, j = col_offset(h, j, self.rows)
        return jax.lax.dynamic_slice(
            f, (off, j), (self.rows, 1)).reshape(self.rows)

    def write_col(self, f, h, j, val):
        """``val``: any R-element block (the callers pass the raw (1, R)
        staging slice; one reshape here, exactly the historical sequence)."""
        off, j = col_offset(h, j, self.rows)
        return jax.lax.dynamic_update_slice(
            f, val.reshape(self.rows, 1), (off, j))

    def stamp_col(self, f, h, j, now):
        off, j = col_offset(h, j, self.rows)
        return jax.lax.dynamic_update_slice(
            f, jnp.full((self.rows, 1), now, f.dtype), (off, j))

    def add_cell(self, f, h, r, j, delta):
        g = global_row(h, r, self.rows)
        cell = jax.lax.dynamic_slice(f, (g, j), (1, 1))
        return jax.lax.dynamic_update_slice(f, cell + delta, (g, j))


@dataclasses.dataclass(frozen=True)
class BlockedLayout:
    """Row-Merge/column-blocked plane storage: (H*Tr, Tc, xr, xc) tiles.

    Per HCU this is exactly `RowMergeLayout(rows, cols, xr, xc).pack`
    (pinned by tests/test_layout.py); network-wide the H per-HCU tile grids
    are stacked along the leading axis, so HCU h's tiles are the Tr
    consecutive tile-rows starting at h*Tr. Pad cells (r >= R or j >= C)
    never feed compute — row/column/cell accessors only ever address valid
    logical coordinates, and `load` slices padding off — so their values are
    free to be garbage (writes fill them with zeros / stamp values).
    """
    rows: int
    cols: int
    xr: int = 8
    xc: int = 4

    @property
    def padded_rows(self) -> int:
        return -(-self.rows // self.xr) * self.xr

    @property
    def padded_cols(self) -> int:
        return -(-self.cols // self.xc) * self.xc

    @property
    def row_tiles_n(self) -> int:        # Tr
        return self.padded_rows // self.xr

    @property
    def col_tiles_n(self) -> int:        # Tc
        return self.padded_cols // self.xc

    @property
    def tpu_degenerate(self) -> bool:
        """One column-tile (xc >= C): the stored form is the row-padded flat
        view (`flat_view`), which the Pallas megakernels consume natively."""
        return self.col_tiles_n == 1

    def plane_shape(self, n_hcu: int):
        return (n_hcu * self.row_tiles_n, self.col_tiles_n, self.xr, self.xc)

    # -- whole-plane conversion (pure data movement, bitwise) ---------------
    def store(self, flat: jnp.ndarray) -> jnp.ndarray:
        """(H*R, C) canonical flat -> (H*Tr, Tc, xr, xc), zero-padded."""
        HR, C = flat.shape
        H = HR // self.rows
        p = flat.reshape(H, self.rows, C)
        p = jnp.pad(p, ((0, 0), (0, self.padded_rows - self.rows),
                        (0, self.padded_cols - C)))
        t = p.reshape(H, self.row_tiles_n, self.xr,
                      self.col_tiles_n, self.xc).transpose(0, 1, 3, 2, 4)
        return t.reshape(H * self.row_tiles_n, self.col_tiles_n,
                         self.xr, self.xc)

    def load(self, stored: jnp.ndarray) -> jnp.ndarray:
        """Inverse of `store`: padding sliced off."""
        H = stored.shape[0] // self.row_tiles_n
        t = stored.reshape(H, self.row_tiles_n, self.col_tiles_n,
                           self.xr, self.xc).transpose(0, 1, 3, 2, 4)
        p = t.reshape(H, self.padded_rows,
                      self.padded_cols)[:, : self.rows, : self.cols]
        return p.reshape(H * self.rows, self.cols)

    # -- traced worklist accessors ------------------------------------------
    def read_row(self, f, g):
        """Global flat row index g -> the logical (1, C) row."""
        h, r = g // self.rows, g % self.rows
        blk = jax.lax.dynamic_slice(
            f, (h * self.row_tiles_n + r // self.xr, 0, r % self.xr, 0),
            (1, self.col_tiles_n, 1, self.xc))
        return blk.reshape(1, self.padded_cols)[:, : self.cols]

    def _row_block(self, val):
        pc = self.padded_cols
        if pc != self.cols:
            val = jnp.pad(val, ((0, 0), (0, pc - self.cols)))
        return val.reshape(1, self.col_tiles_n, 1, self.xc)

    def write_row(self, f, g, val):
        h, r = g // self.rows, g % self.rows
        return jax.lax.dynamic_update_slice(
            f, self._row_block(val.astype(f.dtype)),
            (h * self.row_tiles_n + r // self.xr, 0, r % self.xr, 0))

    def stamp_row(self, f, g, now):
        h, r = g // self.rows, g % self.rows
        return jax.lax.dynamic_update_slice(
            f, jnp.full((1, self.col_tiles_n, 1, self.xc), now, f.dtype),
            (h * self.row_tiles_n + r // self.xr, 0, r % self.xr, 0))

    def read_col(self, f, h, j):
        """HCU h's logical column j -> (R,)."""
        blk = jax.lax.dynamic_slice(
            f, (h * self.row_tiles_n, j // self.xc, 0, j % self.xc),
            (self.row_tiles_n, 1, self.xr, 1))
        return blk.reshape(self.padded_rows)[: self.rows]

    def _col_block(self, val):
        pr = self.padded_rows
        val = val.reshape(self.rows)
        if pr != self.rows:
            val = jnp.pad(val, (0, pr - self.rows))
        return val.reshape(self.row_tiles_n, 1, self.xr, 1)

    def write_col(self, f, h, j, val):
        return jax.lax.dynamic_update_slice(
            f, self._col_block(val.astype(f.dtype)),
            (h * self.row_tiles_n, j // self.xc, 0, j % self.xc))

    def stamp_col(self, f, h, j, now):
        return jax.lax.dynamic_update_slice(
            f, jnp.full((self.row_tiles_n, 1, self.xr, 1), now, f.dtype),
            (h * self.row_tiles_n, j // self.xc, 0, j % self.xc))

    def add_cell(self, f, h, r, j, delta):
        idx = (h * self.row_tiles_n + r // self.xr, j // self.xc,
               r % self.xr, j % self.xc)
        cell = jax.lax.dynamic_slice(f, idx, (1, 1, 1, 1))
        return jax.lax.dynamic_update_slice(f, cell + delta, idx)

    # -- Pallas megakernel plumbing (degenerate point only) -----------------
    def flat_view(self, stored: jnp.ndarray) -> jnp.ndarray:
        """Degenerate (Tc == 1) stored plane as the row-padded flat
        (H*R', C') view — a pure reshape, so the scalar-prefetch kernels
        (kernels/bcpnn_update.py) need no layout variant: only the row
        indices are remapped (`pad_row_index`)."""
        assert self.tpu_degenerate
        return stored.reshape(stored.shape[0] * self.xr, self.xc)

    def from_flat_view(self, view: jnp.ndarray) -> jnp.ndarray:
        return view.reshape(view.shape[0] // self.xr, 1, self.xr, self.xc)

    def pad_row_index(self, g, n_hcu: int):
        """Canonical flat row index (sentinel n_hcu*R) -> row-padded view
        index (sentinel n_hcu*R', which the row kernels skip)."""
        rp = self.padded_rows
        return jnp.where(g < n_hcu * self.rows,
                         (g // self.rows) * rp + g % self.rows,
                         n_hcu * rp)

    def pad_ivec(self, v, n_hcu: int):
        """(H*R,) i-vector -> (H*R',) zero-padded (the fused row megakernel
        shares one row-index stream between planes and i-vectors)."""
        if self.padded_rows == self.rows:
            return v
        return jnp.pad(v.reshape(n_hcu, self.rows),
                       ((0, 0), (0, self.padded_rows - self.rows))) \
            .reshape(-1)

    def unpad_ivec(self, v, n_hcu: int):
        if self.padded_rows == self.rows:
            return v
        return v.reshape(n_hcu, self.padded_rows)[:, : self.rows].reshape(-1)


def as_blocked(layout) -> BlockedLayout | None:
    """Normalize a layout argument for engine/worklist branching: None for
    the flat default (None or FlatLayout), else the BlockedLayout."""
    if layout is None or isinstance(layout, FlatLayout):
        return None
    return layout


def resolve_layout(layout, p) -> BlockedLayout | None:
    """User-facing layout spec -> normalized static-arg form (None == flat).

    Accepts None / "flat" / a PlaneLayout instance / "blocked" (the CPU
    cache-line sweet spot, `cpu_blocked`) / "blocked_tpu" (the (8, 128)
    degenerate point, `tpu_blocked`)."""
    if layout is None or layout == "flat" or isinstance(layout, FlatLayout):
        return None
    if layout == "blocked":
        return cpu_blocked(p)
    if layout == "blocked_tpu":
        return tpu_blocked(p)
    if isinstance(layout, BlockedLayout):
        return layout
    raise ValueError(f"unknown plane layout {layout!r}")


# CPU column-blocked sweet spot (measured at human_col, see
# benchmarks/fig10_rowmerge.py -> BENCH_layout.json): xc*4 B spans a quarter
# cache line, so a fired column touches ~R*xc*4/64 = R/4 lines instead of R,
# while a row pays ceil(C/xc) segments instead of ~7 lines — the right trade
# at the paper's 100:1 row:column *access*-rate but R-cell column size.
CPU_BLOCK_XR = 8
CPU_BLOCK_XC = 4


def cpu_blocked(p) -> BlockedLayout:
    return BlockedLayout(rows=p.rows, cols=p.cols,
                         xr=CPU_BLOCK_XR, xc=CPU_BLOCK_XC)


def tpu_blocked(p) -> BlockedLayout:
    return BlockedLayout(rows=p.rows, cols=p.cols, xr=8, xc=128)


def layout_tag(layout) -> str:
    """Checkpoint-manifest tag for a layout (parse: `layout_from_tag`)."""
    lay = as_blocked(layout)
    if lay is None:
        return "flat"
    return f"blocked:xr={lay.xr},xc={lay.xc}"


def layout_from_tag(tag: str, p) -> BlockedLayout | None:
    if tag in (None, "", "flat"):
        return None
    if tag.startswith("blocked:"):
        kv = dict(kv.split("=") for kv in tag[len("blocked:"):].split(","))
        return BlockedLayout(rows=p.rows, cols=p.cols,
                             xr=int(kv["xr"]), xc=int(kv["xc"]))
    raise ValueError(f"unknown layout tag {tag!r}")


def store_hcus(hcus, layout):
    """Canonical-flat HCUState -> the layout's stored form (ij planes only;
    i-/j-vectors are layout-independent). No-op for flat."""
    lay = as_blocked(layout)
    if lay is None:
        return hcus
    return hcus._replace(**{f: lay.store(getattr(hcus, f))
                            for f in _FLAT_PLANE_FIELDS})


def load_hcus(hcus, layout):
    """Inverse of `store_hcus` (stored form -> canonical flat)."""
    lay = as_blocked(layout)
    if lay is None:
        return hcus
    return hcus._replace(**{f: lay.load(getattr(hcus, f))
                            for f in _FLAT_PLANE_FIELDS})


def convert_hcus(hcus, src, dst):
    """Re-store an HCUState from layout `src` to layout `dst` (either may be
    None == flat). Pure data movement through the canonical flat form —
    logical values are bitwise-preserved (the checkpoint cross-layout
    restore shim, tests/test_checkpoint.py)."""
    s, d = as_blocked(src), as_blocked(dst)
    if s == d:
        return hcus
    return store_hcus(load_hcus(hcus, s), d)
