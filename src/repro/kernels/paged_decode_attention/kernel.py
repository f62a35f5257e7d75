"""Paged GQA decode-attention Pallas TPU kernel.

Continuous-batching serving stores the KV cache as fixed-size *pages* drawn
from a shared pool instead of one dense (B, max_seq) slab per request. Each
request owns a page list (its row of the page table), so KV *memory* tracks
the tokens actually resident, not the engine-wide ``max_seq``. Compute and
DMA track them too, at two levels:

* ``pages_bound`` bounds the walk by the live maximum (ceil(max(seq_lens) /
  page_size), bucketed by the caller so compiles stay bounded) instead of
  the static page-table width (``None`` walks the whole table: the parity
  baseline);
* within the walk, each slot copies and computes only its own live pages:
  blocks wholly past ``ceil(seq_lens[b] / ps)`` (or, for a window layer,
  wholly before the slot's window) cost one empty grid step and no DMA,
  and the last live block copies only its live pages.

Sliding-window layers (gemma3-style local attention) pass a static
``window > 0``: key position ``kpos`` is additionally valid only when it
falls inside the query's trailing window, ``kpos >= seq_lens[b] - window``
(the decode query sits at global position ``seq_lens[b] - 1``). Because the
mask is by *global* position, the walk may also *start* late:
``pages_start`` (static, caller-bucketed) skips pages that no request's
window can reach, so a window layer's walk covers ``[pages_start,
pages_bound)``.

Grid and blocks. The grid is ``(B, NB)``: one step covers every KV head of
a block of ``ppb`` consecutive pages of one slot's page-table row, and the
walk's ``NP = pages_bound - pages_start`` pages make ``NB = ceil(NP /
ppb)`` blocks. In the pool layout one page of all heads is a contiguous
``(K, ps, D)`` slab, so a block is ``ppb`` such DMAs from the HBM pools
(left in place, ``memory_space=pl.ANY``) into VMEM scratch, each looked up
through the page table (scalar-prefetched with the lengths and the layer).
``block_pages`` derives ``ppb`` from the shapes alone: about
``BLOCK_TOKENS`` keys a block, no more pages than the walk has, and the
double-buffered K and V blocks within ``VMEM_BUDGET``. For 16-token pages
of 8 heads x 128 (or 4 x 256) bf16 that is 16 pages, 512 KiB of K and as
much of V a buffer, 2 MiB in all.

Double buffering. The live (slot, block) steps are visited in grid order;
each one starts the DMAs of the *next* live step (the next block of its
slot, or the first live block of the next slot with any) into the other
buffer before it waits for and computes its own, so copies overlap compute
across slots as well as within one. The first live step of the call
fetches its own block; an SMEM flag, set at grid step (0, 0), marks it.

Stale data. Buffer rows of pages that were not copied this step hold
another step's keys or uninitialised VMEM (possibly NaN). Scores are
selected (``jnp.where``, never multiplied) to ``NEG_INF`` outside the valid
keys, and V rows outside them are zeroed before the PV product, since 0 x
NaN is NaN. A fully masked slot (length 0) writes zeros.

Layouts:
  q        (B, K, G, D)   pre-scaled; G = n_heads / n_kv_heads
  k_pages  (L, P, K, ps, D)  shared page pool of every layer (P pages of
  v_pages  (L, P, K, ps, D)  ps tokens); ``layer`` (a traced scalar,
                             prefetched with the page table) picks the
                             layer read, so a layer scan hands the kernel
                             the whole pool and no layer is copied out.
  page_table (B, MP) int32; seq_lens (B,) int32
The kernel never reads a page-table entry outside ``[pages_start,
pages_bound)``: a slot whose length runs past the walk (one the caller
masks) is clamped to it. (m, l, acc) for all K heads accumulate in VMEM
scratch across the sequential block dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 256         # keys a block aims at
VMEM_BUDGET = 4 << 20      # bytes of K and V blocks, double-buffered


def block_pages(page_size: int, n_kv_heads: int, head_dim: int,
                itemsize: int, n_pages: int) -> int:
    """Pages per block (``ppb``) of a walk over ``n_pages`` pages: about
    ``BLOCK_TOKENS`` keys, at most the walk, and two buffers each of K and
    V within ``VMEM_BUDGET``; at least one page."""
    page_bytes = n_kv_heads * page_size * head_dim * itemsize
    return max(1, min(BLOCK_TOKENS // page_size, n_pages,
                      VMEM_BUDGET // (4 * page_bytes)))


def _live_pages(n, *, page_size: int, pages_start: int, end: int,
                window: int, xp=jnp):
    """[lo, hi) pages of a slot of length ``n`` the walk must read,
    clamped to ``[pages_start, end)``; empty when lo >= hi. ``xp`` is
    ``jnp`` inside the kernel, ``np`` for host integers."""
    hi = xp.minimum(xp.maximum((n + page_size - 1) // page_size,
                               pages_start), end)
    if window > 0:
        first = xp.maximum(n - window, 0) // page_size
        lo = xp.minimum(xp.maximum(first, pages_start), hi)
    else:
        lo = xp.minimum(pages_start, hi)
    return lo, hi


def _live_blocks(n, *, ppb: int, pages_start: int, xp=jnp, **walk):
    """[jlo, jhi) blocks of ``ppb`` pages holding the live pages of a slot
    of length ``n`` (``_live_pages``); jhi == jlo when none."""
    lo, hi = _live_pages(n, pages_start=pages_start, xp=xp, **walk)
    jlo = (lo - pages_start) // ppb
    return jlo, xp.where(hi > lo, (hi - pages_start + ppb - 1) // ppb, jlo)


def walk_blocks(seq_lens, *, page_size: int, n_kv_heads: int, head_dim: int,
                itemsize: int, pages_bound: int, pages_start: int = 0,
                window: int = 0):
    """(walked, live) blocks of one kernel call over ``seq_lens`` (host
    integers): walked is every grid step, ``B * NB``; live the steps that
    copy and compute. Mirrors the kernel's block rule and skip."""
    n_pages = pages_bound - pages_start
    ppb = block_pages(page_size, n_kv_heads, head_dim, itemsize, n_pages)
    jlo, jhi = _live_blocks(np.asarray(seq_lens, np.int64), ppb=ppb,
                            pages_start=pages_start, page_size=page_size,
                            end=pages_bound, window=window, xp=np)
    return len(seq_lens) * -(-n_pages // ppb), int((jhi - jlo).sum())


def _paged_kernel(pt_ref, sl_ref, li_ref, q_ref, kp_hbm, vp_hbm, o_ref,
                  kbuf, vbuf, sems, state, m_ref, l_ref, acc_ref, *,
                  page_size: int, window: int, pages_start: int, end: int,
                  ppb: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    n_slots = pl.num_programs(0)
    K = kbuf.shape[2]
    T = ppb * page_size
    walk = dict(page_size=page_size, pages_start=pages_start, end=end,
                window=window)

    def live_pages(s):
        return _live_pages(sl_ref[s], **walk)

    def live_blocks(s):
        return _live_blocks(sl_ref[s], ppb=ppb, **walk)

    def dma(s, blk, slot, *, start: bool):
        """Start (or wait for) the DMAs of the live pages of block ``blk``
        of slot ``s``, into buffer ``slot``: one (K, ps, D) slab of K and
        one of V per page."""
        lo, hi = live_pages(s)
        first = pages_start + blk * ppb

        def body(p, carry):
            page = pt_ref[s, p]
            for i, (pool, buf) in enumerate(((kp_hbm, kbuf), (vp_hbm, vbuf))):
                cp = pltpu.make_async_copy(pool.at[li_ref[0], page],
                                           buf.at[slot, p - first],
                                           sems.at[i, slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(jnp.maximum(first, lo),
                          jnp.minimum(first + ppb, hi), body, 0)

    @pl.when((b == 0) & (j == 0))
    def _first_step():
        state[0] = 0       # buffer of the next live step
        state[1] = 1       # no live step has fetched its own block yet

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    jlo, jhi = live_blocks(b)

    @pl.when((jlo <= j) & (j < jhi))
    def _live():
        slot = state[0]

        @pl.when(state[1] == 1)
        def _fetch_own():
            dma(b, j, slot, start=True)
            state[1] = 0

        def next_slot():
            # first slot after b with a live block, or n_slots
            def body(i, nxt):
                s = n_slots - 1 - i
                lo_s, hi_s = live_pages(s)
                return jnp.where(hi_s > lo_s, s, nxt)
            s = jax.lax.fori_loop(0, n_slots - 1 - b, body, n_slots)
            return s, live_blocks(jnp.minimum(s, n_slots - 1))[0]

        nxt_b, nxt_j = jax.lax.cond(j + 1 < jhi, lambda: (b, j + 1),
                                    next_slot)

        @pl.when(nxt_b < n_slots)
        def _prefetch():
            dma(nxt_b, nxt_j, 1 - slot, start=True)
            state[0] = 1 - slot

        dma(b, j, slot, start=False)
        first = pages_start + j * ppb
        n = sl_ref[b]
        kpos = first * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, T), 1)
        valid = kpos < jnp.minimum(n, end * page_size)
        rows = first * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (T, 1), 0)
        vrow = rows < jnp.minimum(n, end * page_size)
        if window > 0:
            # the decode query sits at global position n - 1; keys older
            # than its trailing window are masked by global position
            valid &= kpos >= n - window
            vrow &= rows >= n - window
        for h in range(K):
            q = q_ref[0, h]                                   # (G, D)
            k = kbuf[slot, :, h].reshape(T, q.shape[-1])      # (T, D)
            v = vbuf[slot, :, h].reshape(T, q.shape[-1])
            # rows of pages not copied this step are stale: select, never
            # multiply, so a NaN there cannot reach the output
            v = jnp.where(vrow, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, NEG_INF)                  # (G, T)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # explicit re-mask: a row whose keys are all masked keeps
            # m_new at NEG_INF, where exp(s - m_new) = 1 would count them
            pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(pexp, axis=1,
                                                  keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_blocks - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def paged_decode_attention_gqa(q, k_pages, v_pages, layer, page_table,
                               seq_lens, *, pages_bound: int | None = None,
                               pages_start: int = 0, window: int = 0,
                               interpret: bool | None = None):
    """q: (B, K, G, D) pre-scaled; k_pages/v_pages: (L, P, K, ps, D);
    ``layer``: int32 scalar (may be traced) naming the layer of the pools
    to read; page_table: (B, MP) int32; seq_lens: (B,) int32.

    ``pages_bound``: static end of the page walk; a slot whose length runs
    past it reads only its first ``pages_bound`` pages (the live-bounded
    dispatch covers every slot it keeps); None walks the full static
    page-table width. ``window``: static sliding-window size (0 = global
    attention) — keys older than the query's trailing ``window`` positions
    are masked by global position. ``pages_start``: static first page of
    the walk (window layers only) — the caller guarantees every kept
    request's first in-window key position is ``>= pages_start * ps``, so
    the walk covers [pages_start, pages_bound).

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
    ppb = block_pages(ps, K, D, k_pages.dtype.itemsize, NP)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, -(-NP // ppb)),
        in_specs=[
            pl.BlockSpec((1, K, G, D), lambda b, j, pt, sl, li: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, K, G, D),
                               lambda b, j, pt, sl, li: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, K, ps, D), k_pages.dtype),
            pltpu.VMEM((2, ppb, K, ps, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page_size=ps, window=window,
                          pages_start=pages_start, end=end, ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_pages, v_pages)
