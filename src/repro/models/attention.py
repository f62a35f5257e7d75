"""Grouped-query attention with RoPE, causal/sliding-window masking,
memory-bounded chunked prefill, and KV-cache decode.

Three execution paths:
  * full forward (train / prefill): query-chunked streaming attention so the
    (S, S) score matrix is never materialised — this is the pure-jnp analogue
    of the Pallas flash_attention kernel (kernels/flash_attention) and is the
    path used by the multi-pod dry-run;
  * Pallas path (cfg.use_pallas): TPU flash-attention kernel;
  * decode: one-token attention against a KV cache, optionally windowed
    (attention-sink + last-W positions) for the long-context serving mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import apply_rope, dense_init, dtype_of

NEG_INF = -1e30


def init_attention(key, cfg) -> dict:
    D, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, (H, Dh), dt),
        "wk": dense_init(ks[1], D, (K, Dh), dt),
        "wv": dense_init(ks[2], D, (K, Dh), dt),
        "wo": dense_init(ks[3], H * Dh, D, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, Dh), dt)
        p["bk"] = jnp.zeros((K, Dh), dt)
        p["bv"] = jnp.zeros((K, Dh), dt)
    return p


def _project_qkv(params, x, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _expand_kv(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(B, S, K, D) -> (B, S, H, D) by repeating each KV head H/K times."""
    K = k.shape[2]
    if K == n_heads:
        return k
    return jnp.repeat(k, n_heads // K, axis=2)


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int) -> jnp.ndarray:
    """Additive bias (Q, Kv) from position grids."""
    allowed = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        allowed &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        allowed &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(allowed, 0.0, NEG_INF)


def attention_forward(params, x, cfg, *, is_global=True, causal=True,
                      positions=None, kv_override=None, use_rope=True) -> jnp.ndarray:
    """Full-sequence attention. x: (B, S, D).

    ``is_global`` may be a python bool or a traced scalar (scanned layer flag);
    False selects the sliding-window mask. ``kv_override``: (k, v) from an
    encoder for cross-attention (positions then index the decoder side only).
    """
    B, S, D = x.shape
    H, Dh = cfg.n_heads, cfg.resolved_head_dim
    if positions is None:
        positions = jnp.arange(S)
    if kv_override is None and use_rope:
        q, k, v = _project_qkv(params, x, cfg, positions)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        if cfg.qkv_bias:
            q = q + params["bq"]
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        if kv_override is not None:
            k, v = kv_override
        else:
            k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
            v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
            if cfg.qkv_bias:
                k = k + params["bk"]
                v = v + params["bv"]
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = Dh ** -0.5

    if cfg.use_pallas and isinstance(is_global, bool):
        from repro.kernels.flash_attention import ops as fa_ops
        window = cfg.sliding_window if (not is_global and cfg.sliding_window) else 0
        return _out_proj(params, fa_ops.flash_attention(
            q * scale, k, v, causal=causal, window=window), B, S, H, Dh)

    kv_len = k.shape[1]
    q_pos = positions if positions.ndim == 1 else positions[0]
    k_pos = jnp.arange(kv_len)

    chunk = min(cfg.attn_chunk, S)
    if S % chunk != 0:
        chunk = S  # irregular sizes (smoke tests): single chunk
    n_chunks = S // chunk

    window = cfg.sliding_window if cfg.sliding_window else 0

    def chunk_attn(carry, idx):
        qs = jax.lax.dynamic_slice_in_dim(q, idx * chunk, chunk, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, idx * chunk, chunk, axis=0)
        scores = jnp.einsum("bqhk,bshk->bhqs", qs, k).astype(jnp.float32) * scale
        bias_local = _mask_bias(qp, k_pos, causal=causal, window=window)
        bias_global = _mask_bias(qp, k_pos, causal=causal, window=0)
        if isinstance(is_global, bool):
            bias = bias_global if is_global else bias_local
        else:
            bias = jnp.where(is_global, bias_global, bias_local)
        scores = scores + bias[None, None]
        w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqs,bshk->bqhk", w, v)
        return carry, o

    if n_chunks == 1:
        _, out = chunk_attn(None, 0)
    else:
        _, outs = jax.lax.scan(chunk_attn, None, jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, Dh)
    return _out_proj(params, out, B, S, H, Dh)


def _out_proj(params, out, B, S, H, Dh):
    return out.reshape(B, S, H * Dh) @ params["wo"]


# ----------------------------------------------------------------------- cache
def init_kv_cache(cfg, batch: int, max_seq: int, n_layers: int, dtype=None):
    K, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = dtype or dtype_of(cfg)
    shape = (n_layers, batch, max_seq, K, Dh)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_paged_kv_cache(cfg, num_pages: int, page_size: int, n_layers: int,
                        dtype=None):
    """Shared page pool: (n_layers, num_pages, K, page_size, Dh) per tensor
    — a page's tokens for one KV head form a trailing (page_size, Dh) tile,
    the block the paged kernels read.

    Page 0 is reserved as the pool's scratch page (writes for inactive slots
    and masked reads land there); allocators hand out pages >= 1.
    """
    K, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = dtype or dtype_of(cfg)
    shape = (n_layers, num_pages, K, page_size, Dh)
    return {"k_pages": jnp.zeros(shape, dt), "v_pages": jnp.zeros(shape, dt)}


def prefill_attention(params, x, cfg, *, is_global=True, positions=None):
    """Prefill: full forward + return this layer's (k, v) for cache insertion."""
    B, S, D = x.shape
    if positions is None:
        positions = jnp.arange(S)
    _, k, v = _project_qkv(params, x, cfg, positions)
    out = attention_forward(params, x, cfg, is_global=is_global, positions=positions)
    return out, (k, v)


def decode_attention(params, x_t, layer_k, layer_v, pos, cfg, *,
                     is_global=True, windowed=False):
    """One decode step.

    x_t: (B, 1, D); layer_k/v: (B, Smax, K, Dh) with entries < pos valid.
    Returns (out (B,1,D), new_k, new_v). ``windowed``: long-context serving
    mode — attend only to an attention-sink prefix + the trailing W positions.
    """
    B = x_t.shape[0]
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    positions = jnp.full((B, 1), pos)
    q, k_t, v_t = _project_qkv(params, x_t, cfg, positions)

    from repro.sharding.context import flash_decode_ctx
    fctx = flash_decode_ctx()
    if (fctx is not None and not windowed
            and isinstance(is_global, bool) and is_global
            and layer_k.shape[1] % fctx[0].shape["model"] == 0):
        out, layer_k, layer_v = _flash_decode_seq_sharded(
            q * (Dh ** -0.5), layer_k, layer_v, k_t, v_t, pos, H, *fctx)
        return _out_proj(params, out, B, 1, H, Dh), layer_k, layer_v
    layer_k = jax.lax.dynamic_update_slice_in_dim(layer_k, k_t, pos, axis=1)
    layer_v = jax.lax.dynamic_update_slice_in_dim(layer_v, v_t, pos, axis=1)
    scale = Dh ** -0.5

    def attend(keys, vals, key_positions):
        kk = _expand_kv(keys, H)
        vv = _expand_kv(vals, H)
        valid = key_positions <= pos
        if cfg.sliding_window:
            in_window = (pos - key_positions) < cfg.sliding_window
            if isinstance(is_global, bool):
                if not is_global:
                    valid &= in_window
            else:
                valid &= jnp.where(is_global, True, in_window)
        if cfg.use_pallas:
            from repro.kernels.decode_attention import ops as da_ops
            return da_ops.decode_attention(q * scale, kk, vv, valid)
        scores = jnp.einsum("bqhk,bshk->bhqs", q, kk).astype(jnp.float32) * scale
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(vv.dtype)
        return jnp.einsum("bhqs,bshk->bqhk", w, vv)

    if windowed:
        W = min(cfg.long_context_window, layer_k.shape[1])
        sink = min(cfg.attention_sink, layer_k.shape[1])
        start = jnp.clip(pos - W + 1, 0, layer_k.shape[1] - W)
        k_win = jax.lax.dynamic_slice_in_dim(layer_k, start, W, axis=1)
        v_win = jax.lax.dynamic_slice_in_dim(layer_v, start, W, axis=1)
        win_pos = start + jnp.arange(W)
        k_sink = layer_k[:, :sink]
        v_sink = layer_v[:, :sink]
        sink_pos = jnp.arange(sink)
        # Avoid double-counting: sink positions may overlap the window at small pos.
        sink_pos_masked = jnp.where(sink_pos < start, sink_pos, pos + 1)  # invalid->masked
        keys = jnp.concatenate([k_sink, k_win], axis=1)
        vals = jnp.concatenate([v_sink, v_win], axis=1)
        kpos = jnp.concatenate([sink_pos_masked, win_pos])
        out = attend(keys, vals, kpos)
    else:
        out = attend(layer_k, layer_v, jnp.arange(layer_k.shape[1]))
    return _out_proj(params, out, B, 1, H, Dh), layer_k, layer_v


def paged_decode_attention(params, x_t, k_pages, v_pages, layer, page_table,
                           seq_lens, active, cfg, pages_bound=None, *,
                           window=0, pages_start=0):
    """One decode step against a paged KV cache (continuous batching).

    x_t: (B, 1, D) — one new token per serving slot. k_pages/v_pages:
    (L, P, K, ps, Dh) shared pools of every attention layer, of which this
    is ``layer`` (int32 scalar, traced under a layer scan) — the new K/V is
    written into that layer in place and the kernel reads it there, so no
    layer's pool is sliced out; page_table: (B, MP); seq_lens: (B,) tokens
    already in each slot's cache (the new token lands at index seq_lens);
    active: (B,) bool — inactive slots write to the reserved scratch page 0,
    read no pages (their length is 0), and their output is masked by the
    engine. ``pages_bound``: static live bound on the kernel's page walk
    (the engine computes it from its seq_lens snapshot; every active
    slot's context must fit); None = the full static page-table width.
    ``window``: this layer's static sliding
    window (0 = global); ``pages_start``: static first walked page for
    window layers (every active slot's first in-window key must be
    ``>= pages_start * ps``; must be 0 when ``window`` is 0).

    Returns (out (B, 1, D), k_pages, v_pages).
    """
    B = x_t.shape[0]
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ps = k_pages.shape[3]
    MP = page_table.shape[1]
    cap = MP * ps
    pos = jnp.minimum(seq_lens, cap - 1)                  # write position
    q, k_t, v_t = _project_qkv(params, x_t, cfg, pos[:, None])
    page = page_table[jnp.arange(B), pos // ps]           # (B,)
    page = jnp.where(active, page, 0)                     # scratch for idle
    # every index explicit, so the scatter's window is the trailing Dh
    # row alone and the pool keeps the row-major layout the kernel reads
    heads = jnp.arange(K)[None, :]
    at = (layer, page[:, None], heads, (pos % ps)[:, None])
    k_pages = k_pages.at[at].set(k_t[:, 0])
    v_pages = v_pages.at[at].set(v_t[:, 0])
    # incl. the new token; 0 for idle slots, so the kernel skips them
    lens = jnp.where(active, jnp.minimum(seq_lens + 1, cap), 0)
    scale = Dh ** -0.5
    qg = (q[:, 0] * scale).reshape(B, K, H // K, Dh)
    if cfg.use_pallas:
        from repro.kernels.paged_decode_attention.kernel import \
            paged_decode_attention_gqa
        out = paged_decode_attention_gqa(qg, k_pages, v_pages, layer,
                                         page_table, lens,
                                         pages_bound=pages_bound,
                                         pages_start=pages_start,
                                         window=window)
    else:
        from repro.kernels.paged_decode_attention.ref import \
            paged_decode_attention_ref
        out = paged_decode_attention_ref(qg, k_pages[layer], v_pages[layer],
                                         page_table,
                                         lens, pages_bound=pages_bound,
                                         pages_start=pages_start,
                                         window=window)
    out = out.reshape(B, 1, H, Dh)
    return _out_proj(params, out, B, 1, H, Dh), k_pages, v_pages


def paged_prefill_attention(params, x, k_pages, v_pages, layer, page_table,
                            start, n_new, cfg, pages_bound=None, *, window=0,
                            pages_start=0):
    """One chunked-prefill step against a paged KV cache.

    x: (B, C, D) — a fixed-width chunk of prompt activations per serving
    slot, of which the first ``n_new[b]`` rows are real tokens (the rest is
    bucket padding). k_pages/v_pages: (L, P, K, ps, Dh) shared pools of
    every attention layer, of which this is ``layer`` (int32 scalar);
    page_table: (B, MP) the slot's page-table row; start: (B,) tokens
    already resident (the chunk occupies global positions
    ``start .. start + n_new - 1``).

    Writes the chunk's K/V projections directly into the pool pages covering
    those positions (padding rows land on the reserved scratch page 0), then
    attends each chunk query causally to the resident context plus the
    in-chunk keys via the paged prefill kernel. ``pages_bound``: static live
    bound on the kernel's page walk (every ``start + n_new`` must fit); None
    = the full static page-table width. ``window``: this layer's static
    sliding window (0 = global); ``pages_start``: static first walked page
    for window layers (every row's earliest in-window key,
    ``start - window + 1``, must be ``>= pages_start * ps``). Returns
    (out (B, C, D), k_pages, v_pages).
    """
    B, C, D = x.shape
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ps = k_pages.shape[3]
    MP = page_table.shape[1]
    cap = MP * ps
    positions = start[:, None] + jnp.arange(C)[None, :]       # (B, C)
    q, k_c, v_c = _project_qkv(params, x, cfg, positions)
    # scatter the chunk's K/V into its pages: valid rows go to their page,
    # padding rows (c >= n_new) to the scratch page 0
    pos = jnp.minimum(positions, cap - 1)
    valid = jnp.arange(C)[None, :] < n_new[:, None]
    page = jnp.take_along_axis(page_table, pos // ps, axis=1)  # (B, C)
    page = jnp.where(valid, page, 0)
    at = (layer, page[:, :, None], jnp.arange(K)[None, None, :],
          (pos % ps)[:, :, None])
    k_pages = k_pages.at[at].set(k_c)
    v_pages = v_pages.at[at].set(v_c)
    total = start + n_new
    scale = Dh ** -0.5
    G = H // K
    qg = jnp.transpose((q * scale).reshape(B, C, K, G, Dh), (0, 2, 1, 3, 4))
    if cfg.use_pallas:
        from repro.kernels.paged_prefill_attention.kernel import \
            paged_prefill_attention_gqa
        out = paged_prefill_attention_gqa(qg, k_pages, v_pages, layer,
                                          page_table, start, total,
                                          pages_bound=pages_bound,
                                          pages_start=pages_start,
                                          window=window)
    else:
        from repro.kernels.paged_prefill_attention.ref import \
            paged_prefill_attention_ref
        out = paged_prefill_attention_ref(qg, k_pages[layer], v_pages[layer],
                                          page_table,
                                          start, total,
                                          pages_bound=pages_bound,
                                          pages_start=pages_start,
                                          window=window)
    out = jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, C, H, Dh)
    return _out_proj(params, out, B, C, H, Dh), k_pages, v_pages


def _flash_decode_seq_sharded(q, layer_k, layer_v, k_t, v_t, pos, n_heads,
                              mesh, batch_axes=None):
    """Flash-decode over a sequence-sharded KV cache (shard_map over "model").

    q: (B, 1, H, Dh) pre-scaled; layer_k/v: (B, S, K, Dh) sharded S->"model"
    (batch optionally on ``batch_axes``); k_t/v_t: (B, 1, K, Dh). Each seq
    shard computes local max/denominator/weighted-sum; global combination is
    two psums of (B, H, 1|Dh) — O(MB) instead of gathering the cache. The
    cache update happens shard-locally (the owner shard writes the new KV).
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    S = layer_k.shape[1]
    m_size = mesh.shape["model"]
    S_loc = S // m_size
    ba = batch_axes

    def local(q, k, v, kt, vt, pos):
        mi = jax.lax.axis_index("model")
        start = mi * S_loc
        owns = (pos >= start) & (pos < start + S_loc)
        li = jnp.clip(pos - start, 0, S_loc - 1)
        k = jnp.where(owns, jax.lax.dynamic_update_slice_in_dim(k, kt, li, 1), k)
        v = jnp.where(owns, jax.lax.dynamic_update_slice_in_dim(v, vt, li, 1), v)
        kk = _expand_kv(k, n_heads)
        vv = _expand_kv(v, n_heads)
        s = jnp.einsum("bqhk,bshk->bhqs", q, kk).astype(jnp.float32)
        kpos = start + jnp.arange(S_loc)
        s = jnp.where((kpos <= pos)[None, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1, keepdims=True)            # (B,H,1,1)
        m_glob = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m_glob)
        l_loc = jnp.sum(p, axis=-1, keepdims=True)
        o_loc = jnp.einsum("bhqs,bshk->bqhk", p.astype(vv.dtype), vv
                           ).astype(jnp.float32)
        l = jax.lax.psum(l_loc, "model")                      # (B,H,1,1)
        o = jax.lax.psum(o_loc, "model")                      # (B,1,H,Dh)
        out = o / jnp.maximum(l[:, :, 0, :, None].transpose(0, 2, 1, 3), 1e-30)
        return out.astype(q.dtype), k, v

    q4 = P(ba, None, None, None)
    kv = P(ba, "model", None, None)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(q4, kv, kv, q4, q4, P()),
                   out_specs=(q4, kv, kv), check_rep=False)
    return fn(q, layer_k, layer_v, k_t, v_t, pos)


def decode_cross_attention(params, x_t, enc_k, enc_v, cfg):
    """Cross-attention decode step against fixed encoder memory (no cache update)."""
    B = x_t.shape[0]
    H, Dh = cfg.n_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x_t, params["wq"])
    kk = _expand_kv(enc_k, H)
    vv = _expand_kv(enc_v, H)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, kk).astype(jnp.float32) * (Dh ** -0.5)
    w = jax.nn.softmax(scores, axis=-1).astype(vv.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", w, vv)
    return _out_proj(params, out, B, 1, H, Dh)
