"""Pallas TPU flash-attention (forward) — fused online-softmax attention.

The LM-side perf-critical kernel: never materializes the (Sq, Skv) logits in
HBM. Grid = (batch*heads, q_blocks, kv_blocks); the kv dimension is the
innermost (sequential) axis, carrying the running (max, denom, accumulator)
in VMEM scratch across kv steps — Pallas double-buffers the K/V tile DMA
against the MXU matmuls, the same ping-pong structure as the BCPNN update
kernel (and the paper's EQ3 k=2 design point).

Supports causal masking, sliding windows and logit softcap (gemma2).
Validated against ref.py / the dense jnp oracle in interpret mode
(tests/test_flash_attention.py); `repro.models.layers` uses it when
cfg.attn_impl == "pallas_flash" on a TPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, causal: bool, window: int | None,
                  softcap: float | None, bq: int, bk: int, n_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                     # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < kvlen_ref[0, 0]        # dynamic cache-validity bound
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "softcap", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    window: int | None = None, softcap: float | None = None,
                    kv_len=None, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False):
    """q: (BH, Sq, hd), k/v: (BH, Skv, hd) -> (BH, Sq, hd).

    GQA callers fold (batch, kv_head, group) into BH with k/v broadcast.
    Sq % bq == 0 and Skv % bk == 0 required (caller pads). kv_len (dynamic
    int32 scalar) bounds the valid cache prefix; defaults to Skv.
    """
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    n_q, n_k = Sq // bq, Skv // bk
    grid = (BH, n_q, n_k)
    if kv_len is None:
        kv_len = Skv
    kv_arr = jnp.asarray(kv_len, jnp.int32).reshape(1, 1)
    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             window=window, softcap=softcap, bq=bq, bk=bk,
                             n_k=n_k)
    scratch = [
        _new_scratch((bq, 1), jnp.float32),
        _new_scratch((bq, 1), jnp.float32),
        _new_scratch((bq, hd), jnp.float32),
    ]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(kv_arr, q, k, v)


def _new_scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


def flash_attention_ref(q, k, v, *, scale: float, causal: bool = True,
                        window: int | None = None,
                        softcap: float | None = None):
    """Dense jnp oracle with identical masking semantics."""
    logits = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    Sq, Skv = q.shape[1], k.shape[1]
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    logits = jnp.where(mask[None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
