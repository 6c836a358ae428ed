"""Compile the BCPNN Pallas kernels for a TPU v5e that is described, not
attached.

Interpret mode (tests/test_kernels.py) checks what the kernels compute; it
cannot check what Mosaic accepts. These tests lower every kernel of the tick
path at the paper's rodent (R=1200, C=70) and human (R=10000, C=100) widths
for one chip of a described `v5e:2x2` topology, through the same `ops`
wrappers the engine calls, and assert that the compiled program holds the
kernel (`tpu_custom_call`). A block shape, an SMEM operand or a scratch
buffer the chip's compiler refuses fails here, without a chip.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hcu as H
from repro.core.params import human_scale, rodent_scale
from repro.kernels import ops

# (params, HCUs per chip): human widths at the one-chip smoke size, rodent
# widths at a comparable plane footprint
SCALES = {"human": (human_scale(128), 128), "rodent": (rodent_scale(256), 256)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    # a compile for a described device is written to the persistent cache
    # but can never be read back without the chip; keep these compiles out
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(p, n):
    """Call shapes of each kernel wrapper at one tick of an n-HCU network:
    A = delay bucket + external slots per HCU, W = n*A worklist entries,
    K = the default fired-batch capacity."""
    f32, i32 = jnp.float32, jnp.int32
    R, C = p.rows, p.cols
    HR, A = n * R, p.active_queue + 24
    W, K = n * A, max(2, int(0.35 * n) + 1)
    plane = [((HR, C), f32)] * 4 + [((HR, C), i32)]
    row = [((A, C), f32)] * 3 + [((A, C), i32)]
    col = [((R,), f32)] * 3 + [((R,), i32)]
    return {
        "worklist": plane + [((W,), i32), ((), i32), ((), i32), ((W,), f32),
                             ((W, C), f32), ((W,), f32), ((W, C), f32)],
        "fused_row": plane + [((HR,), f32)] * 3 + [((HR,), i32)]
        + [((W,), i32), ((), i32), ((W,), f32), ((W, C), f32), ((W,), f32),
           ((W, C), f32)] + [((W,), f32)] * 3,
        "fused_col": plane + [((K,), i32), ((K,), i32), ((), i32),
                              ((K, R), f32), ((K, R), f32), ((K,), f32)],
        "dense_row": row + [((), i32), ((A,), f32), ((C,), f32),
                            ((A,), f32), ((C,), f32)],
        "dense_col": col + [((), i32), ((R,), f32), ((R,), f32), ((), f32)],
    }


def _call(kernel, p, n):
    k, eps = H.coeffs_ij(p), p.eps
    kw = dict(coeffs=k, eps=eps, backend="pallas")
    if kernel == "worklist":
        return lambda *a: ops.worklist_row_update(*a, **kw)
    if kernel == "fused_row":
        return lambda *a: ops.fused_row_update(*a, **kw)
    if kernel == "fused_col":
        return lambda *a: ops.fused_col_update(*a, n_hcu=n, rows=p.rows,
                                               **kw)
    if kernel == "dense_row":
        return lambda z, e, pp, t, now, cnt, zj, pi, pj: ops.row_update(
            z, e, pp, t, now, cnt, zj, pi, pj, **kw)
    return lambda z, e, pp, t, now, zi, pi, pj: ops.col_update(
        z, e, pp, t, now, zi, pi, pj, **kw)


@pytest.mark.parametrize("scale", sorted(SCALES))
@pytest.mark.parametrize("kernel", ["worklist", "fused_row", "fused_col",
                                    "dense_row", "dense_col"])
def test_kernel_compiles_for_v5e(kernel, scale, one_chip, no_cache):
    p, n = SCALES[scale]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in _shapes(p, n)[kernel]]
    compiled = jax.jit(_call(kernel, p, n)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_col_compiles_for_v5e_past_one_lane_tile(one_chip, no_cache):
    """The column megakernel at the benchmark's rodent cell, 1,152 HCUs: a
    404-slot fired batch, so the presynaptic traces span four lane tiles
    (block (rb, e // 128)) and the SMEM and prefetch arrays hold 404
    entries."""
    p, n = rodent_scale(1152), 1152
    shapes = _shapes(p, n)["fused_col"]
    assert shapes[5] == ((404,), jnp.int32)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(_call("fused_col", p, n)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
