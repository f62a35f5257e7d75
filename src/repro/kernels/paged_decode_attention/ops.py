"""Jit'd public wrapper for paged GQA decode attention.

Model layout in: q (B, H, D) pre-scaled (one new token per request slot),
the shared page pools of every layer (L, P, K, ps, D) and the layer to
read, the per-slot page table (B, MP) and sequence lengths (B,). Regroups q to the kernel's (B, K, G, D) GQA layout.
"""
from __future__ import annotations

import functools

import jax

from .kernel import paged_decode_attention_gqa

# The family's threaded compile keys: static args carried kernel <-> ops <->
# ref. ``repro.analysis.pallas_check`` verifies this declaration matches the
# jit decorator below, that the kernel entry declares each name, and that
# the ref oracle exercises it.
STATIC_ARGS = ("pages_bound", "pages_start", "window")


@functools.partial(jax.jit, static_argnames=("pages_bound", "pages_start",
                                             "window"))
def paged_decode_attention(q, k_pages, v_pages, layer, page_table, seq_lens,
                           pages_bound=None, pages_start=0, window=0):
    """q: (B, H, D) pre-scaled; k_pages/v_pages: (L, P, K, ps, D); layer:
    int32 scalar; page_table: (B, MP); seq_lens: (B,). ``pages_bound``: static live bound
    on the page walk (None = full static width); ``window``/``pages_start``:
    static sliding-window size (0 = global) and first walked page (window
    layers only). Returns (B, H, D)."""
    B, H, D = q.shape
    K = k_pages.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, D)  # heads are grouped per KV head (GQA order)
    out = paged_decode_attention_gqa(qg, k_pages, v_pages, layer,
                                     page_table, seq_lens, pages_bound=pages_bound,
                                     pages_start=pages_start, window=window)
    return out.reshape(B, H, D)
