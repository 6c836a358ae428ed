"""Pallas kernel vs pure-jnp oracle, swept over shapes/dtypes (interpret mode).

Per-kernel allclose against ref.py as required: the kernel body executes in
Python on CPU via interpret=True; on a real TPU the same pallas_call lowers
to Mosaic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.traces import make_coeffs
from repro.kernels import ops

K = make_coeffs(2.5, 100.0, 1000.0)
EPS = 1e-4


def _row_args(rng, S, C, tmax=100):
    return dict(
        zij=jnp.asarray(rng.uniform(0, 2, (S, C)), jnp.float32),
        eij=jnp.asarray(rng.uniform(0, 2, (S, C)), jnp.float32),
        pij=jnp.asarray(rng.uniform(1e-3, 1, (S, C)), jnp.float32),
        tij=jnp.asarray(rng.integers(0, tmax, (S, C)), jnp.int32),
        now=tmax,
        counts=jnp.asarray(rng.integers(0, 4, (S,)), jnp.float32),
        zj=jnp.asarray(rng.uniform(0, 2, (C,)), jnp.float32),
        p_i=jnp.asarray(rng.uniform(1e-3, 1, (S,)), jnp.float32),
        p_j=jnp.asarray(rng.uniform(1e-3, 1, (C,)), jnp.float32),
    )


@pytest.mark.parametrize("S,C", [(1, 1), (3, 17), (8, 100), (36, 100),
                                 (5, 128), (16, 256), (40, 100)])
def test_row_kernel_matches_ref_shapes(S, C):
    rng = np.random.default_rng(S * 1000 + C)
    a = _row_args(rng, S, C)
    ref = ops.row_update(**a, coeffs=K, eps=EPS, backend="ref")
    pal = ops.row_update(**a, coeffs=K, eps=EPS, backend="pallas_interpret")
    for r, p_, name in zip(ref, pal, "zepwt"):
        np.testing.assert_allclose(r, p_, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name} S={S} C={C}")


@pytest.mark.parametrize("R", [1, 100, 300, 1024, 1200, 2048])
def test_col_kernel_matches_ref_shapes(R):
    rng = np.random.default_rng(R)
    args = dict(
        z_col=jnp.asarray(rng.uniform(0, 2, (R,)), jnp.float32),
        e_col=jnp.asarray(rng.uniform(0, 2, (R,)), jnp.float32),
        p_col=jnp.asarray(rng.uniform(1e-3, 1, (R,)), jnp.float32),
        t_col=jnp.asarray(rng.integers(0, 60, (R,)), jnp.int32),
        now=60,
        zi_t=jnp.asarray(rng.uniform(0, 2, (R,)), jnp.float32),
        p_i=jnp.asarray(rng.uniform(1e-3, 1, (R,)), jnp.float32),
        p_j_scalar=0.37,
    )
    ref = ops.col_update(**args, coeffs=K, eps=EPS, backend="ref")
    pal = ops.col_update(**args, coeffs=K, eps=EPS, backend="pallas_interpret")
    for r, p_, name in zip(ref, pal, "zepwt"):
        np.testing.assert_allclose(r, p_, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name} R={R}")


@settings(max_examples=25, deadline=None)
@given(s=st.integers(1, 12), c=st.integers(1, 40),
       seed=st.integers(0, 2**31 - 1), now=st.integers(1, 10_000))
def test_row_kernel_property_sweep(s, c, seed, now):
    rng = np.random.default_rng(seed)
    a = _row_args(rng, s, c, tmax=now)
    ref = ops.row_update(**a, coeffs=K, eps=EPS, backend="ref")
    pal = ops.row_update(**a, coeffs=K, eps=EPS, backend="pallas_interpret")
    for r, p_ in zip(ref, pal):
        np.testing.assert_allclose(r, p_, rtol=1e-5, atol=1e-5)


def test_kernel_coeff_variants():
    """Different tau triplets (e.g. rodent vs human presets) stay correct."""
    for taus in [(2.5, 100.0, 1000.0), (5.0, 50.0, 500.0), (1.0, 20.0, 5000.0)]:
        k = make_coeffs(*taus)
        rng = np.random.default_rng(hash(taus) % 2**31)
        a = _row_args(rng, 8, 100)
        ref = ops.row_update(**a, coeffs=k, eps=EPS, backend="ref")
        pal = ops.row_update(**a, coeffs=k, eps=EPS,
                             backend="pallas_interpret")
        for r, p_ in zip(ref, pal):
            np.testing.assert_allclose(r, p_, rtol=3e-6, atol=3e-6)


def test_padding_cells_do_not_leak():
    """Padded lanes/rows must not alter logical outputs: results for a
    (S, C) block must be independent of the padding added to reach tiles."""
    rng = np.random.default_rng(0)
    a = _row_args(rng, 9, 37)          # forces both-dim padding
    out_a = ops.row_update(**a, coeffs=K, eps=EPS,
                           backend="pallas_interpret")
    # same logical content embedded in a bigger call via ref on exact shapes
    out_b = ops.row_update(**a, coeffs=K, eps=EPS, backend="ref")
    for x, y in zip(out_a, out_b):
        assert x.shape == y.shape == (9, 37)
        np.testing.assert_allclose(x, y, rtol=3e-6, atol=3e-6)


def _worklist_args(rng, HR, C, W, rows_list, nv, tmax=100):
    rows = jnp.asarray(list(rows_list) + [HR] * (W - len(rows_list)),
                       jnp.int32)
    return dict(
        zij=jnp.asarray(rng.uniform(0, 2, (HR, C)), jnp.float32),
        eij=jnp.asarray(rng.uniform(0, 2, (HR, C)), jnp.float32),
        pij=jnp.asarray(rng.uniform(1e-3, 1, (HR, C)), jnp.float32),
        wij=jnp.asarray(rng.uniform(-1, 1, (HR, C)), jnp.float32),
        tij=jnp.asarray(rng.integers(0, tmax, (HR, C)), jnp.int32),
        rows=rows, nv=nv, now=tmax,
        counts=jnp.asarray(rng.integers(0, 4, (W,)), jnp.float32),
        zj=jnp.asarray(rng.uniform(0, 2, (W, C)), jnp.float32),
        p_i=jnp.asarray(rng.uniform(1e-3, 1, (W,)), jnp.float32),
        pj=jnp.asarray(rng.uniform(1e-3, 1, (W, C)), jnp.float32),
    )


def _worklist_expected(a, HR, C, nv):
    """Per-entry bcpnn_ref oracle applied to the touched rows only."""
    from repro.kernels import bcpnn_ref
    exp = [np.array(a[k]) for k in ("zij", "eij", "pij", "wij", "tij")]
    for e in range(nv):
        r = int(a["rows"][e])
        z1, e1, p1, w1, t1 = bcpnn_ref.row_update_ref(
            a["zij"][r:r + 1], a["eij"][r:r + 1], a["pij"][r:r + 1],
            a["tij"][r:r + 1], a["now"], a["counts"][e:e + 1], a["zj"][e],
            a["p_i"][e:e + 1], a["pj"][e], K, EPS)
        for plane, val in zip(exp, (z1, e1, p1, w1, t1)):
            plane[r] = np.asarray(val)[0]
    return exp


@pytest.mark.parametrize("HR,C,W,rows,nv", [
    (32, 128, 8, (3, 7, 11, 30), 4),       # aligned, no padding
    (256, 16, 24, (1, 4, 66, 89, 128, 199, 255), 7),   # lane padding
    (40, 100, 8, (0, 39), 2),              # both-dim padding
    (32, 128, 8, (), 0),                   # empty worklist
])
def test_worklist_kernel_matches_ref(HR, C, W, rows, nv):
    """Scalar-prefetch worklist kernel (interpret mode) vs per-row oracle:
    touched rows update, untouched rows (and rows aliased by padding
    entries) stay bit-identical."""
    rng = np.random.default_rng(HR * 1000 + C)
    a = _worklist_args(rng, HR, C, W, rows, nv)
    out = ops.worklist_row_update(**a, coeffs=K, eps=EPS,
                                  backend="pallas_interpret")
    exp = _worklist_expected(a, HR, C, nv)
    untouched = np.setdiff1d(np.arange(HR), np.asarray(rows[:nv], int))
    for o, ex, name in zip(out, exp, "zepwt"):
        o = np.asarray(o)
        np.testing.assert_allclose(o, ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name}")
        # untouched rows must be EXACTLY preserved (in-place contract)
        np.testing.assert_array_equal(o[untouched], ex[untouched],
                                      err_msg=f"untouched rows, plane {name}")


def test_worklist_kernel_padding_entries_are_noops():
    """Entries at/past nv (incl. the H*R sentinel) must not perturb any row
    even when clipped onto real row indices."""
    rng = np.random.default_rng(0)
    a = _worklist_args(rng, 32, 128, 8, (1, 4), 2)
    # poison the padding entries with in-range rows that are also touched
    a["rows"] = jnp.asarray([1, 4, 1, 4, 0, 31, 32, 32], jnp.int32)
    out = ops.worklist_row_update(**a, coeffs=K, eps=EPS,
                                  backend="pallas_interpret")
    exp = _worklist_expected(a, 32, 128, 2)
    for o, ex, name in zip(out, exp, "zepwt"):
        np.testing.assert_allclose(np.asarray(o), ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name}")


def _fused_args(rng, HR, C, W, rows_list, tmax=100):
    """Slot-ordered args for the fused megakernel: `rows` carries the HR
    sentinel on invalid slots (no compaction)."""
    rows = jnp.asarray(list(rows_list) + [HR] * (W - len(rows_list)),
                       jnp.int32)
    return dict(
        zij=jnp.asarray(rng.uniform(0, 2, (HR, C)), jnp.float32),
        eij=jnp.asarray(rng.uniform(0, 2, (HR, C)), jnp.float32),
        pij=jnp.asarray(rng.uniform(1e-3, 1, (HR, C)), jnp.float32),
        wij=jnp.asarray(rng.uniform(-1, 1, (HR, C)), jnp.float32),
        tij=jnp.asarray(rng.integers(0, tmax, (HR, C)), jnp.int32),
        zi=jnp.asarray(rng.uniform(0, 2, (HR,)), jnp.float32),
        ei=jnp.asarray(rng.uniform(0, 2, (HR,)), jnp.float32),
        pi=jnp.asarray(rng.uniform(1e-3, 1, (HR,)), jnp.float32),
        ti=jnp.asarray(rng.integers(0, tmax, (HR,)), jnp.int32),
        rows=rows, now=tmax,
        counts=jnp.asarray(rng.integers(0, 4, (W,)), jnp.float32),
        zj=jnp.asarray(rng.uniform(0, 2, (W, C)), jnp.float32),
        p_i=jnp.asarray(rng.uniform(1e-3, 1, (W,)), jnp.float32),
        pj=jnp.asarray(rng.uniform(1e-3, 1, (W, C)), jnp.float32),
        zi_new=jnp.asarray(rng.uniform(0, 3, (W,)), jnp.float32),
        ei_new=jnp.asarray(rng.uniform(0, 2, (W,)), jnp.float32),
        pi_new=jnp.asarray(rng.uniform(1e-3, 1, (W,)), jnp.float32),
    )


def _fused_expected(a, HR, C, W):
    """Per-entry bcpnn_ref oracle for the fused megakernel: planes, the
    in-place i-vector rewrite and the per-slot weight-row output."""
    from repro.kernels import bcpnn_ref
    exp = [np.array(a[k]) for k in ("zij", "eij", "pij", "wij", "tij")]
    iv = [np.array(a[k]) for k in ("zi", "ei", "pi", "ti")]
    w_rows = np.zeros((W, C), np.float32)
    for e in range(W):
        r = int(a["rows"][e])
        if r >= HR:
            continue
        z1, e1, p1, w1, t1 = bcpnn_ref.row_update_ref(
            a["zij"][r:r + 1], a["eij"][r:r + 1], a["pij"][r:r + 1],
            a["tij"][r:r + 1], a["now"], a["counts"][e:e + 1], a["zj"][e],
            a["p_i"][e:e + 1], a["pj"][e], K, EPS)
        for plane, val in zip(exp, (z1, e1, p1, w1, t1)):
            plane[r] = np.asarray(val)[0]
        iv[0][r] = float(a["zi_new"][e])
        iv[1][r] = float(a["ei_new"][e])
        iv[2][r] = float(a["pi_new"][e])
        iv[3][r] = a["now"]
        w_rows[e] = np.asarray(w1)[0]
    return exp, iv, w_rows


@pytest.mark.parametrize("HR,C,W,rows", [
    (32, 128, 8, (3, 7, 11, 30)),          # aligned, no padding
    (256, 16, 24, (1, 4, 66, 89, 128, 199, 255)),      # lane padding
    (40, 100, 8, (0, 39)),                 # both-dim padding
    (32, 128, 8, ()),                      # empty worklist
])
def test_fused_megakernel_matches_ref(HR, C, W, rows):
    """The fused row-phase megakernel (interpret mode) vs the per-row
    oracle: ij planes, i-vectors and the per-slot weight rows all match;
    untouched rows / i-vector cells stay EXACTLY preserved (in-place
    aliasing contract)."""
    rng = np.random.default_rng(HR * 1000 + C)
    a = _fused_args(rng, HR, C, W, rows)
    flats, ivecs, w_out = ops.fused_row_update(
        **a, coeffs=K, eps=EPS, backend="pallas_interpret")
    exp, iv_exp, w_exp = _fused_expected(a, HR, C, W)
    untouched = np.setdiff1d(np.arange(HR), np.asarray(rows, int))
    for o, ex, name in zip(flats, exp, "zepwt"):
        o = np.asarray(o)
        np.testing.assert_allclose(o, ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name}")
        np.testing.assert_array_equal(o[untouched], ex[untouched],
                                      err_msg=f"untouched rows, plane {name}")
    for o, ex, name in zip(ivecs, iv_exp, ("zi", "ei", "pi", "ti")):
        # i-vector writes are pure data movement -> exact everywhere
        np.testing.assert_array_equal(np.asarray(o), ex,
                                      err_msg=f"i-vector {name}")
    np.testing.assert_allclose(np.asarray(w_out), w_exp, rtol=3e-6,
                               atol=3e-6, err_msg="weight rows")


def _fused_col_args(rng, H_, R, C, cap, fired, tmax=100):
    """Fired-batch args for the fused column megakernel: `fired` is a list
    of (h, j) pairs; padding slots carry h == H_ (the select_fired
    sentinel)."""
    HR = H_ * R
    h_idx = jnp.asarray([h for h, _ in fired] + [H_] * (cap - len(fired)),
                        jnp.int32)
    j_idx = jnp.asarray([j for _, j in fired] + [0] * (cap - len(fired)),
                        jnp.int32)
    return dict(
        zij=jnp.asarray(rng.uniform(0, 2, (HR, C)), jnp.float32),
        eij=jnp.asarray(rng.uniform(0, 2, (HR, C)), jnp.float32),
        pij=jnp.asarray(rng.uniform(1e-3, 1, (HR, C)), jnp.float32),
        wij=jnp.asarray(rng.uniform(-1, 1, (HR, C)), jnp.float32),
        tij=jnp.asarray(rng.integers(0, tmax, (HR, C)), jnp.int32),
        h_idx=h_idx, j_idx=j_idx, now=tmax,
        zi_t=jnp.asarray(rng.uniform(0, 2, (cap, R)), jnp.float32),
        p_i=jnp.asarray(rng.uniform(1e-3, 1, (cap, R)), jnp.float32),
        pj_sc=jnp.asarray(rng.uniform(1e-3, 1, (cap,)), jnp.float32),
    )


def _fused_col_expected(a, H_, R, cap):
    """Per-entry bcpnn_ref column oracle applied to the fired (R, 1) column
    blocks of the flat planes only."""
    from repro.kernels import bcpnn_ref
    exp = [np.array(a[k]) for k in ("zij", "eij", "pij", "wij", "tij")]
    for e in range(cap):
        h, j = int(a["h_idx"][e]), int(a["j_idx"][e])
        if h >= H_:
            continue
        sl = slice(h * R, (h + 1) * R)
        z1, e1, p1, w1, t1 = bcpnn_ref.col_update_ref(
            a["zij"][sl, j], a["eij"][sl, j], a["pij"][sl, j],
            a["tij"][sl, j], a["now"], a["zi_t"][e], a["p_i"][e],
            a["pj_sc"][e], K, EPS)
        for plane, val in zip(exp, (z1, e1, p1, w1, t1)):
            plane[sl, j] = np.asarray(val)
    return exp


@pytest.mark.parametrize("H_,R,C,fired", [
    (4, 32, 128, [(0, 3), (2, 100), (3, 127)]),   # lane-aligned C
    (3, 40, 100, [(1, 0), (2, 99)]),              # lane padding (junk col)
    (2, 64, 16, []),                              # nothing fired
])
def test_fused_col_megakernel_matches_ref(H_, R, C, fired):
    """The fused column-phase megakernel (interpret mode) vs the per-column
    oracle: fired (R, 1) column blocks update (Tij stamped in-kernel),
    every untouched cell stays EXACTLY preserved (in-place aliasing
    contract)."""
    rng = np.random.default_rng(H_ * 1000 + R)
    cap = 6
    a = _fused_col_args(rng, H_, R, C, cap, fired)
    out = ops.fused_col_update(
        a["zij"], a["eij"], a["pij"], a["wij"], a["tij"],
        h_idx=a["h_idx"], j_idx=a["j_idx"], now=a["now"],
        zi_t=a["zi_t"], p_i=a["p_i"], pj_sc=a["pj_sc"],
        coeffs=K, eps=EPS, n_hcu=H_, rows=R,
        backend="pallas_interpret")
    exp = _fused_col_expected(a, H_, R, cap)
    touched = np.zeros((H_ * R, C), bool)
    for h, j in fired:
        touched[h * R:(h + 1) * R, j] = True
    for o, ex, name in zip(out, exp, "zepwt"):
        o = np.asarray(o)
        np.testing.assert_allclose(o, ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name}")
        np.testing.assert_array_equal(o[~touched], ex[~touched],
                                      err_msg=f"untouched cells, plane {name}")


def test_fused_col_megakernel_padding_entries_are_noops():
    """Padding fired-batch entries (h_idx == n_hcu, the select_fired
    sentinel) must not perturb ANY cell even when their j_idx aliases a
    genuinely fired column — the junk-lane rerouting plus the in-kernel
    valid gate make them pass-throughs."""
    rng = np.random.default_rng(2)
    H_, R, C, cap = 3, 32, 100, 6
    a = _fused_col_args(rng, H_, R, C, cap, [(0, 7), (2, 50)])
    # poison the padding entries: in-range (h, j) pairs that alias fired and
    # unfired columns alike — only the h_idx == H_ sentinel marks them
    a["h_idx"] = jnp.asarray([0, 2, H_, H_, H_, H_], jnp.int32)
    a["j_idx"] = jnp.asarray([7, 50, 7, 50, 0, 99], jnp.int32)
    out = ops.fused_col_update(
        a["zij"], a["eij"], a["pij"], a["wij"], a["tij"],
        h_idx=a["h_idx"], j_idx=a["j_idx"], now=a["now"],
        zi_t=a["zi_t"], p_i=a["p_i"], pj_sc=a["pj_sc"],
        coeffs=K, eps=EPS, n_hcu=H_, rows=R,
        backend="pallas_interpret")
    exp = _fused_col_expected(a, H_, R, cap)
    for o, ex, name in zip(out, exp, "zepwt"):
        np.testing.assert_allclose(np.asarray(o), ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name}")


@pytest.mark.parametrize("C", [70, 100])
def test_fused_col_megakernel_batch_past_one_lane_tile(C):
    """A fired batch of 200 slots spans two lane tiles of the presynaptic
    trace buffers: valid entries on both sides of slot 128 read their own
    lane of their own tile, and the padding entries between them (poisoned
    with (h, j) pairs that alias fired and unfired columns) stay no-ops;
    every untouched cell stays EXACTLY preserved."""
    rng = np.random.default_rng(C)
    H_, R, cap = 6, 16, 200
    a = _fused_col_args(rng, H_, R, C, cap, [])
    slots = {3: (0, 5), 127: (1, C - 1), 128: (2, 0), 130: (3, 64),
             199: (5, 5)}
    h_idx = np.full(cap, H_, np.int32)
    j_idx = rng.integers(0, C, cap).astype(np.int32)
    j_idx[rng.integers(0, cap, 40)] = 5           # alias a fired column
    for e, (h, j) in slots.items():
        h_idx[e], j_idx[e] = h, j
    a["h_idx"], a["j_idx"] = jnp.asarray(h_idx), jnp.asarray(j_idx)
    out = ops.fused_col_update(
        a["zij"], a["eij"], a["pij"], a["wij"], a["tij"],
        h_idx=a["h_idx"], j_idx=a["j_idx"], now=a["now"],
        zi_t=a["zi_t"], p_i=a["p_i"], pj_sc=a["pj_sc"],
        coeffs=K, eps=EPS, n_hcu=H_, rows=R,
        backend="pallas_interpret")
    exp = _fused_col_expected(a, H_, R, cap)
    touched = np.zeros((H_ * R, C), bool)
    for h, j in slots.values():
        touched[h * R:(h + 1) * R, j] = True
    for o, ex, name in zip(out, exp, "zepwt"):
        o = np.asarray(o)
        np.testing.assert_allclose(o, ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name}")
        np.testing.assert_array_equal(o[~touched], ex[~touched],
                                      err_msg=f"untouched cells, plane {name}")
    # every fired column was rewritten: its Tij carries the `now` stamp
    assert (np.asarray(out[4])[touched] == a["now"]).all()


def test_fused_megakernel_sentinel_slots_are_noops():
    """Interleaved sentinel slots (slot order, no compaction) must leave
    every plane row and i-vector cell untouched, and emit zero weight rows
    for those slots."""
    rng = np.random.default_rng(1)
    HR, C, W = 32, 128, 8
    a = _fused_args(rng, HR, C, W, ())
    # valid slots 1 and 5; everything else the HR sentinel
    a["rows"] = jnp.asarray([HR, 3, HR, HR, HR, 17, HR, HR], jnp.int32)
    flats, ivecs, w_out = ops.fused_row_update(
        **a, coeffs=K, eps=EPS, backend="pallas_interpret")
    exp, iv_exp, w_exp = _fused_expected(a, HR, C, W)
    for o, ex, name in zip(flats, exp, "zepwt"):
        np.testing.assert_allclose(np.asarray(o), ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"plane {name}")
    for o, ex, name in zip(ivecs, iv_exp, ("zi", "ei", "pi", "ti")):
        np.testing.assert_array_equal(np.asarray(o), ex,
                                      err_msg=f"i-vector {name}")
    assert np.all(np.asarray(w_out)[[0, 2, 3, 4, 6, 7]] == 0.0), \
        "sentinel slots must emit zero weight rows"
    np.testing.assert_allclose(np.asarray(w_out), w_exp, rtol=3e-6, atol=3e-6)


def test_worklist_kernels_same_tile_rows_not_adjacent():
    """Two worklist rows of one (8, 128) tile that are NOT adjacent in entry
    order — rows 3 and 5 with row 17 between them, plus rows 5 and 6 split
    by sentinel slots — must both land, in both row kernels, and the fused
    kernel's i-vector lane group that rows 3..6 share must keep every
    write. A kernel that staged whole tiles through the block pipeline
    would compute the later entry from, or write back, a copy of the tile
    taken before the earlier entry's write. The kernels instead move each
    row through VMEM with synchronous DMAs on the aliased buffers, so the
    chip runs the sequence of reads and writes that this test checks."""
    rng = np.random.default_rng(7)
    HR, C, W = 32, 100, 8
    a = _worklist_args(rng, HR, C, W, (3, 17, 5, 6), 4)
    out = ops.worklist_row_update(**a, coeffs=K, eps=EPS,
                                  backend="pallas_interpret")
    exp = _worklist_expected(a, HR, C, 4)
    for o, ex, name in zip(out, exp, "zepwt"):
        np.testing.assert_allclose(np.asarray(o), ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"worklist plane {name}")
    for r in (3, 5, 6):
        assert np.all(np.asarray(out[4])[r] == a["now"]), f"row {r} stamped"

    a = _fused_args(rng, HR, C, W, ())
    # slot order: tile 0 (rows 3, 5, 6) revisited around tile 2 and
    # sentinel slots; rows 3..6 also share one i-vector lane group
    a["rows"] = jnp.asarray([3, HR, 17, 5, HR, 6, HR, HR], jnp.int32)
    flats, ivecs, w_out = ops.fused_row_update(
        **a, coeffs=K, eps=EPS, backend="pallas_interpret")
    exp, iv_exp, w_exp = _fused_expected(a, HR, C, W)
    for o, ex, name in zip(flats, exp, "zepwt"):
        np.testing.assert_allclose(np.asarray(o), ex, rtol=3e-6, atol=3e-6,
                                   err_msg=f"fused plane {name}")
    for o, ex, name in zip(ivecs, iv_exp, ("zi", "ei", "pi", "ti")):
        np.testing.assert_array_equal(np.asarray(o), ex,
                                      err_msg=f"i-vector {name}")
    np.testing.assert_allclose(np.asarray(w_out), w_exp, rtol=3e-6,
                               atol=3e-6)
