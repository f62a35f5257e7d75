"""Pure-jnp oracle for the paged GQA prefill-attention kernel.

Gathers each request's pages through its page-table row into a dense
(B, MP*ps) key space and runs causally-masked attention for the chunk's
query rows — semantically identical to the kernel, used both as the test
oracle and as the non-Pallas model path. Like the kernel, it assumes the
chunk's K/V are already resident in the pool (the model layer writes them
before attending).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_prefill_attention_ref(q, k_pages, v_pages, page_table, start,
                                total, pages_bound=None, pages_start=0,
                                window=0):
    """q: (B, K, C, G, D) pre-scaled; k_pages/v_pages: (P, K, ps, D);
    page_table: (B, MP) int32; start/total: (B,) int32. ``pages_bound``:
    static live bound on the page walk (every ``total`` must fit in that
    many pages); None gathers the full table width. ``window``: static
    sliding-window size (0 = global), masked by global position.
    ``pages_start``: first walked page (window layers only; every request's
    earliest in-window key must be ``>= pages_start * ps``).
    Returns (B, K, C, G, D)."""
    B, K, C, G, D = q.shape
    ps = k_pages.shape[2]
    assert pages_start == 0 or window > 0, (pages_start, window)
    end = page_table.shape[1] if pages_bound is None else pages_bound
    page_table = page_table[:, pages_start:end]
    MP = page_table.shape[1]
    S = MP * ps
    # (B, MP, K, ps, D) -> (B, K, MP*ps, D)
    k = jnp.moveaxis(k_pages[page_table], 2, 1).reshape(B, K, S, D)
    v = jnp.moveaxis(v_pages[page_table], 2, 1).reshape(B, K, S, D)
    s = jnp.einsum("bkcgd,bksd->bkcgs", q, k).astype(jnp.float32)
    kpos = pages_start * ps + jnp.arange(S)
    qpos = start[:, None] + jnp.arange(C)                     # (B, C)
    valid = (kpos[None, None, :] <= qpos[:, :, None]) \
        & (kpos[None, None, :] < total[:, None, None])        # (B, C, S)
    if window > 0:
        valid &= (qpos[:, :, None] - kpos[None, None, :]) < window
    s = jnp.where(valid[:, None, :, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    # fully-masked query rows (chunk padding past n_new) softmax to uniform
    # garbage; zero them the way the kernel's re-mask does
    w = jnp.where(valid[:, None, :, None, :], w, 0.0)
    return jnp.einsum("bkcgs,bksd->bkcgd", w.astype(v.dtype), v)
