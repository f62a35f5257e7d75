"""Paged GQA prefill-attention Pallas TPU kernel (chunked prefill).

Chunked prefill admits a prompt into the continuous-batching engine one
fixed-width chunk at a time instead of one-shot, so live decode slots keep
stepping while a long prompt streams in, and ragged admission compiles one
shape per bucketed chunk width instead of one per distinct prompt length.

The caller has already written the chunk's K/V projections into the pool
pages covering positions ``start .. start + n_new - 1`` (see
models.attention.paged_prefill_attention), so this kernel is a pure reader,
exactly like its decode sibling (kernels/paged_decode_attention): the page
table plus the per-request ``start`` / ``total`` lengths arrive as
*scalar-prefetch* operands, the K/V BlockSpec index maps resolve the physical
page id for grid position (b, h, p) before the block DMA is issued, and the
(m, l, acc) online-softmax statistics carry across the sequential trailing
page dim in VMEM scratch.

Masking is causal by *global* position: chunk query row ``c`` sits at
position ``start + c``, and key position ``p * page_size + i`` is valid iff
it is ``<= start + c`` (causal — this covers both the resident context and
the in-chunk keys) and ``< total`` (pages past the written prefix may point
anywhere, conventionally scratch page 0, and are fully masked). Padded query
rows (``c >= n_new``) produce garbage the caller slices off.

Sliding-window layers pass a static ``window > 0``: a key is additionally
valid only inside its query's trailing window,
``start + c - kpos < window`` — the same global-position mask the dense
reference applies. ``pages_start`` (static, caller-bucketed) then lets the
walk skip pages no query's window can reach (every request's earliest
in-window key, ``start - window + 1``, must be >= ``pages_start * ps``), so
windowed prefill compute scales with the window, not the resident prefix.
Fully-masked (query, page) pairs are re-masked after the online-softmax max
so they contribute exactly zero.

Layouts:
  q        (B, K, C, G, D)  pre-scaled chunk queries; G = n_heads / n_kv_heads
  k_pages  (L, P, K, ps, D) shared page pool of every layer (P pages of ps
  v_pages  (L, P, K, ps, D) tokens); one page's slice for one KV head is a
                            whole trailing (ps, D) tile, and ``layer`` (a
                            prefetched scalar) picks the layer read
  page_table (B, MP) int32; start (B,) int32; total (B,) int32
Grid = (B, K, pages_bound or MP); q is flattened to (B, K, C*G, D) rows
(c-major) so each grid step is one (C*G, ps) score tile. ``pages_bound``
bounds the sequential page walk by the live maximum (ceil(max(total) /
page_size), bucketed by the caller) so compute tracks the tokens actually
resident, not the engine-wide static page-table width; ``pages_bound=None``
keeps the full static walk (the parity baseline).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_prefill_kernel(pt_ref, st_ref, tl_ref, li_ref, q_ref, k_ref, v_ref,
                          o_ref, m_ref, l_ref, acc_ref, *, page_size: int,
                          group: int, window: int, pages_start: int):
    b = pl.program_id(0)
    p = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]        # (CG, D) chunk-row-major: row = c * group + g
    k = k_ref[0, 0, 0]     # (ps, D)
    v = v_ref[0, 0, 0]     # (ps, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (CG, ps)

    CG = s.shape[0]
    kpos = (pages_start + p) * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (CG, page_size), 1)
    qpos = st_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, (CG, page_size), 0) // group
    valid = (kpos <= qpos) & (kpos < tl_ref[b])
    if window > 0:
        valid &= qpos - kpos < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # explicit re-mask: a (query, page) pair with no valid key keeps
    # m_new at NEG_INF, where exp(s - m_new) = 1 would count masked keys
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(pexp, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == np_ - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def paged_prefill_attention_gqa(q, k_pages, v_pages, layer, page_table,
                                start, total, *,
                                pages_bound: int | None = None,
                                pages_start: int = 0, window: int = 0,
                                interpret: bool | None = None):
    """q: (B, K, C, G, D) pre-scaled; k_pages/v_pages: (L, P, K, ps, D);
    ``layer``: int32 scalar (may be traced) naming the layer of the pools
    to read; page_table: (B, MP) int32; start/total: (B,) int32 (tokens
    resident before the chunk / after it: ``total = start + n_new``).

    ``pages_bound``: static bound on the sequential page walk — the caller
    guarantees every ``total`` fits in ``pages_bound`` pages (live-bounded
    dispatch); None walks the full static page-table width. ``window``:
    static sliding-window size (0 = global) — keys outside a query's
    trailing window are masked by global position. ``pages_start``: static
    first page of the walk (window layers only) — the caller guarantees
    every request's earliest in-window key (``start - window + 1``) is
    ``>= pages_start * ps``.

    Returns (B, K, C, G, D). ``interpret=None`` auto-detects the backend.
    """
    from repro.kernels.common import default_interpret
    interpret = default_interpret(interpret)
    B, K, C, G, D = q.shape
    _, _, Kk, ps, Dk = k_pages.shape
    assert (Kk, Dk) == (K, D), (k_pages.shape, q.shape)
    MP = page_table.shape[1]
    end = MP if pages_bound is None else pages_bound
    assert window >= 0 and pages_start >= 0, (window, pages_start)
    assert pages_start == 0 or window > 0, \
        "pages_start > 0 is only sound under a sliding window"
    NP = end - pages_start
    assert 1 <= NP and end <= MP, (pages_bound, pages_start, MP)
    CG = C * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, K, NP),
        in_specs=[
            pl.BlockSpec((1, 1, CG, D),
                         lambda b, h, p, pt, st, tl, li: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, ps, D),
                         lambda b, h, p, pt, st, tl, li:
                         (li[0], pt[b, pages_start + p], h, 0, 0)),
            pl.BlockSpec((1, 1, 1, ps, D),
                         lambda b, h, p, pt, st, tl, li:
                         (li[0], pt[b, pages_start + p], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, CG, D),
                               lambda b, h, p, pt, st, tl, li: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, page_size=ps, group=G,
                          window=window, pages_start=pages_start),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, CG, D), q.dtype),
        interpret=interpret,
        name="paged_prefill_attention",
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      total.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(B, K, CG, D), k_pages, v_pages)
    return out.reshape(B, K, C, G, D)
