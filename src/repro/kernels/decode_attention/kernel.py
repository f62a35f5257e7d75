"""GQA decode-attention Pallas TPU kernel.

One new token attends to a KV cache. TPU-native GQA layout: instead of
expanding KV to n_heads (bandwidth waste — decode is memory-bound), the
kernel works per KV head with the query *group* (G = n_heads / n_kv_heads)
as the sublane dim: q block (G, D) vs K block (bk, D) -> scores (G, bk).
This reads each cache byte exactly once — the core insight for a decode
kernel on a memory-bandwidth-limited chip.

Grid = (B * K, k_blocks); (m, l, acc) accumulate in VMEM scratch across the
sequential trailing grid dim. A per-position validity mask (pos <= current,
window) arrives as an int32 (BK, 1, S) row blocked alongside K: a
(1, 1, bk) block keeps the trailing two block dims equal to the array's
or 128-aligned, which the TPU's compiler requires ((1, bk) over (BK, S)
is refused), and the v5e's vector unit compares 32-bit, not int8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref,
                *, bk: int):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]          # (G, D)
    k = k_ref[0]          # (bk, D)
    v = v_ref[0]          # (bk, D)
    valid = valid_ref[0]  # (1, bk) int32
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bk)
    s = jnp.where(valid > 0, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention_gqa(q, k, v, valid, *, bk: int = 512,
                         interpret: bool | None = None):
    """q: (BK, G, D) pre-scaled; k, v: (BK, S, D); valid: (BK, S) int.

    Returns (BK, G, D). BK = batch * n_kv_heads; G = n_heads / n_kv_heads.
    Irregular S is padded up to a block multiple (padding arrives masked via
    ``valid``); ``interpret=None`` auto-detects the backend.
    """
    from repro.kernels.common import default_interpret
    interpret = default_interpret(interpret)
    BK, G, D = q.shape
    S = k.shape[1]
    bk = min(bk, S)
    pad = (-S) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
        S += pad
    grid = (BK, S // bk)
    return pl.pallas_call(
        functools.partial(_dec_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, ki: (b, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, ki: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BK, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(q, k, v, valid.astype(jnp.int32)[:, None, :])
