"""Paged GQA decode-attention Pallas TPU kernel.

Continuous-batching serving stores the KV cache as fixed-size *pages* drawn
from a shared pool instead of one dense (B, max_seq) slab per request. Each
request owns a page list (its row of the page table), so KV *memory* tracks
the tokens actually resident, not the engine-wide ``max_seq``. Compute
tracks it too: ``pages_bound`` bounds the sequential page dim by the live
maximum (ceil(max(seq_lens) / page_size), bucketed by the caller so compiles
stay bounded) instead of gridding over the static page-table width; per-slot
masking still handles ragged lengths within the bound, and pages past a
request's length resolve to the reserved scratch page and are fully masked.
``pages_bound=None`` keeps the full static walk (the parity baseline).

Sliding-window layers (gemma3-style local attention) pass a static
``window > 0``: key position ``kpos`` is additionally valid only when it
falls inside the query's trailing window, ``kpos >= seq_lens[b] - window``
(the decode query sits at global position ``seq_lens[b] - 1``). Because the
mask is by *global* position, the walk may also *start* late:
``pages_start`` (static, caller-bucketed) skips pages that no request's
window can reach, so a window layer's page walk covers
``[pages_start, pages_bound)`` instead of ``[0, pages_bound)`` — dead
prefix pages cost nothing. A page that is fully masked for one request
(its window starts later than the shared walk) contributes nothing: the
masked probabilities are zeroed explicitly, so the online-softmax
statistics never see the exp(NEG_INF - NEG_INF) = 1 degeneracy.

This kernel extends the dense GQA decode kernel (kernels/decode_attention)
with that gather: the page table and per-request sequence lengths arrive as
*scalar-prefetch* operands (``PrefetchScalarGridSpec``), so the K/V
BlockSpec index maps can look up the physical page id for grid position
(b, h, p) before the block DMA is issued — the canonical TPU paged-attention
pattern. Masking: key position ``p * page_size + i`` is valid iff it is
``< seq_lens[b]``; page-table entries past a request's length may point
anywhere (conventionally page 0, the pool's reserved scratch page) and are
fully masked.

Layouts:
  q        (B, K, G, D)   pre-scaled; G = n_heads / n_kv_heads
  k_pages  (L, P, K, ps, D)  shared page pool of every layer (P pages of
  v_pages  (L, P, K, ps, D)  ps tokens); one page's slice for one KV head
                             is a whole trailing (ps, D) tile, the block
                             the TPU's compiler accepts (a (ps, 1, D) slice
                             is not). ``layer`` (a traced scalar, prefetched
                             with the page table) picks the layer read, so
                             a layer scan hands the kernel the whole pool
                             and no layer's pages are ever copied out.
  page_table (B, MP) int32; seq_lens (B,) int32
Grid = (B, K, pages_bound or MP); (m, l, acc) accumulate in VMEM scratch
across the sequential trailing page dim, exactly like the dense decode
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pt_ref, sl_ref, li_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, window: int,
                  pages_start: int):
    b = pl.program_id(0)
    p = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]        # (G, D)
    k = k_ref[0, 0, 0]     # (ps, D)
    v = v_ref[0, 0, 0]     # (ps, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, ps)
    kpos = (pages_start + p) * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = kpos < sl_ref[b]
    if window > 0:
        # the decode query sits at global position sl_ref[b] - 1; keys
        # older than its trailing window are masked by global position
        valid &= kpos >= sl_ref[b] - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # explicit re-mask: on a fully-masked page m_new can still be NEG_INF,
    # and exp(NEG_INF - NEG_INF) = 1 would count masked keys into l/acc
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


def paged_decode_attention_gqa(q, k_pages, v_pages, layer, page_table,
                               seq_lens, *, pages_bound: int | None = None,
                               pages_start: int = 0, window: int = 0,
                               interpret: bool | None = None):
    """q: (B, K, G, D) pre-scaled; k_pages/v_pages: (L, P, K, ps, D);
    ``layer``: int32 scalar (may be traced) naming the layer of the pools
    to read; page_table: (B, MP) int32; seq_lens: (B,) int32.

    ``pages_bound``: static bound on the sequential page walk — the caller
    guarantees every seq_len fits in ``pages_bound`` pages (live-bounded
    dispatch); None walks the full static page-table width. ``window``:
    static sliding-window size (0 = global attention) — keys older than the
    query's trailing ``window`` positions are masked by global position.
    ``pages_start``: static first page of the walk (window layers only) —
    the caller guarantees every request's first in-window key position is
    ``>= pages_start * ps``, so the walk covers [pages_start, pages_bound).

    Returns (B, K, G, D). ``interpret=None`` auto-detects the backend.
    """
    from repro.kernels.common import default_interpret
    interpret = default_interpret(interpret)
    B, K, G, D = q.shape
    _, _, Kk, ps, Dk = k_pages.shape
    assert (Kk, Dk) == (K, D), (k_pages.shape, q.shape)
    MP = page_table.shape[1]
    end = MP if pages_bound is None else pages_bound
    assert window >= 0 and pages_start >= 0, (window, pages_start)
    assert pages_start == 0 or window > 0, \
        "pages_start > 0 is only sound under a sliding window"
    NP = end - pages_start
    assert 1 <= NP and end <= MP, (pages_bound, pages_start, MP)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, K, NP),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, p, pt, sl, li: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, ps, D),
                         lambda b, h, p, pt, sl, li:
                         (li[0], pt[b, pages_start + p], h, 0, 0)),
            pl.BlockSpec((1, 1, 1, ps, D),
                         lambda b, h, p, pt, sl, li:
                         (li[0], pt[b, pages_start + p], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, pt, sl, li: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page_size=ps, window=window,
                          pages_start=pages_start),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_pages, v_pages)
