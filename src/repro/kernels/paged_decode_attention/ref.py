"""Pure-jnp oracle for the paged GQA decode-attention kernel.

Gathers each request's pages through its page-table row into a dense
(B, MP*ps) key space and runs masked attention — semantically identical to
the kernel, used both as the test oracle and as the non-Pallas model path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, seq_lens,
                               pages_bound=None, pages_start=0, window=0):
    """q: (B, K, G, D) pre-scaled; k_pages/v_pages: (P, K, ps, D);
    page_table: (B, MP) int32; seq_lens: (B,) int32. ``pages_bound``: static
    live bound on the page walk (every seq_len must fit in that many pages);
    None gathers the full table width. ``window``: static sliding-window
    size (0 = global) — keys older than the query's trailing ``window``
    positions are masked by global position. ``pages_start``: first walked
    page (window layers only; every first in-window key must be
    ``>= pages_start * ps``). Returns (B, K, G, D)."""
    B, K, G, D = q.shape
    ps = k_pages.shape[2]
    assert pages_start == 0 or window > 0, (pages_start, window)
    end = page_table.shape[1] if pages_bound is None else pages_bound
    page_table = page_table[:, pages_start:end]
    MP = page_table.shape[1]
    # (B, MP, K, ps, D) -> (B, K, MP*ps, D)
    k = jnp.moveaxis(k_pages[page_table], 2, 1).reshape(B, K, MP * ps, D)
    v = jnp.moveaxis(v_pages[page_table], 2, 1).reshape(B, K, MP * ps, D)
    s = jnp.einsum("bkgd,bksd->bkgs", q, k).astype(jnp.float32)
    kpos = pages_start * ps + jnp.arange(MP * ps)
    valid = kpos[None] < seq_lens[:, None]                     # (B, MP*ps)
    if window > 0:
        valid &= kpos[None] >= seq_lens[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    # a fully-masked row (window entirely before the walk start of an idle
    # slot) softmaxes to uniform garbage; zero it like the kernel does
    w = jnp.where(valid[:, None, None, :], w, 0.0)
    return jnp.einsum("bkgs,bksd->bkgd", w.astype(v.dtype), v)
