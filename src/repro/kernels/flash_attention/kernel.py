"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax attention with O(S) memory. Grid = (batch*heads, q_blocks,
k_blocks); the TPU executes the trailing grid dim sequentially per core, so
the (m, l, acc) running statistics live in VMEM scratch and carry across the
k dimension — the canonical TPU flash pattern. Block shapes are MXU-aligned
(multiples of 128 on the contraction dims when the head_dim allows).

Supports causal masking and an optional sliding window (for gemma3-style
local layers). Inputs are pre-scaled q (caller multiplies by 1/sqrt(d)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
               *, bq: int, bk: int, causal: bool, window: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                      # (bq, d)
    k = k_ref[0]                      # (bk, d)
    v = v_ref[0]                      # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < kv_len  # padded tail of an irregular S is never attended
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]               # (bq, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)            # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)   # (bq, 1)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         bq: int = 128, bk: int = 128,
                         interpret: bool | None = None):
    """q, k, v: (BH, S, D) with q pre-scaled. Returns (BH, S, D).

    Irregular S is padded to a block multiple internally (padded key columns
    are masked, padded query rows sliced off); ``interpret=None`` auto-detects
    the backend.
    """
    from repro.kernels.common import default_interpret
    interpret = default_interpret(interpret)
    BH, S, D = q.shape
    bq = min(bq, S)
    bk = min(bk, S)
    if S % bq or S % bk:
        blk = max(bq, bk)
        bq = bk = blk
        Sp = ((S + blk - 1) // blk) * blk
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))
    Sp = q.shape[1]
    grid = (BH, Sp // bq, Sp // bk)
    kernel = functools.partial(_fa_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, kv_len=S)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :S] if Sp != S else out
