"""Jit'd public wrapper for paged GQA prefill attention (chunked prefill).

Model layout in: q (B, C, H, D) pre-scaled (one chunk of C query tokens per
request), the shared page pools of every layer (L, P, K, ps, D) and the layer
to read, the request's page-table row(s) (B, MP), and the per-request
start/total lengths. Regroups q to the kernel's
(B, K, C, G, D) GQA layout (heads grouped per KV head).

This chunked-prefill shape doubles as the speculative *verify* shape: a
draft chunk of gamma+1 candidate tokens scored by the target model is
exactly one prefill chunk with explicit (start, n_new) — causal over the
chunk, attending to everything the page table already holds — so cross-tier
speculative decoding (serving.engine.attach_draft) reuses this launch
verbatim and needs no third kernel. The explicit ``start`` operand (rather
than reading seq_lens) is what lets the engine pre-advance its length
bookkeeping before dispatch and roll a rejected suffix back afterwards.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import paged_prefill_attention_gqa

# The family's threaded compile keys: static args carried kernel <-> ops <->
# ref. ``repro.analysis.pallas_check`` verifies this declaration matches the
# jit decorator below, that the kernel entry declares each name, and that
# the ref oracle exercises it.
STATIC_ARGS = ("pages_bound", "pages_start", "window")


@functools.partial(jax.jit, static_argnames=("pages_bound", "pages_start",
                                             "window"))
def paged_prefill_attention(q, k_pages, v_pages, layer, page_table, start,
                            total, pages_bound=None, pages_start=0, window=0):
    """q: (B, C, H, D) pre-scaled; k_pages/v_pages: (L, P, K, ps, D);
    layer: int32 scalar; page_table: (B, MP); start/total: (B,). ``pages_bound``: static live
    bound on the page walk (None = full static width); ``window``/
    ``pages_start``: static sliding-window size (0 = global) and first
    walked page (window layers only). Returns (B, C, H, D)."""
    B, C, H, D = q.shape
    K = k_pages.shape[2]
    G = H // K
    qg = jnp.transpose(q.reshape(B, C, K, G, D), (0, 2, 1, 3, 4))
    out = paged_prefill_attention_gqa(qg, k_pages, v_pages, layer,
                                      page_table, start, total, pages_bound=pages_bound,
                                      pages_start=pages_start, window=window)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, C, H, D)
