"""Decoder-only LM covering the dense / moe / ssm / vlm families (plus the
gemma3 5:1 local:global sliding-window pattern).

Layer stacks are ``jax.lax.scan``-ned with weights stacked on a leading layer
axis — this keeps the HLO one-layer-sized so 64–88 layer production configs
compile quickly in the AOT dry-run. Heterogeneity across layers (gemma3's
local/global flag) is passed as scanned per-layer data, not as separate param
structures.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import attention as attn
from . import moe as moe_lib
from . import ssm as ssm_lib
from .common import (dtype_of, embed, init_embedding, init_mlp, init_rmsnorm,
                     mlp, rmsnorm, unembed)
from repro.sharding.context import constrain_batch


# ------------------------------------------------------------------- layer init
def init_layer(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    if cfg.family == "ssm":
        return {"ln": init_rmsnorm(cfg.d_model, dt),
                "ssm": ssm_lib.init_ssm(ks[0], cfg)}
    p = {"ln1": init_rmsnorm(cfg.d_model, dt),
         "attn": attn.init_attention(ks[0], cfg),
         "ln2": init_rmsnorm(cfg.d_model, dt)}
    if cfg.n_experts > 0:
        p["moe"] = moe_lib.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dt)
    return p


def init_decoder(key, cfg) -> dict:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    # vmapped over the layer keys: the same values as stacking per-layer
    # inits, traced (and compiled) once rather than n_layers times
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    params = {
        "embed": init_embedding(k_emb, cfg),
        "layers": layers,
        "ln_f": init_rmsnorm(cfg.d_model, dtype_of(cfg)),
    }
    if not cfg.tie_embeddings:
        from .common import init_output_head
        params["head"] = init_output_head(k_head, cfg)
    return params


# ---------------------------------------------------------------- layer apply
def _layer_forward(layer_p, x, cfg, is_global, positions):
    """One layer, full-sequence. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        h = rmsnorm(layer_p["ln"], x, cfg.norm_eps)
        return x + ssm_lib.ssm_forward(layer_p["ssm"], h, cfg), aux
    h = rmsnorm(layer_p["ln1"], x, cfg.norm_eps)
    x = x + attn.attention_forward(layer_p["attn"], h, cfg,
                                   is_global=is_global, positions=positions)
    h = rmsnorm(layer_p["ln2"], x, cfg.norm_eps)
    if cfg.n_experts > 0:
        y, aux = moe_lib.moe_forward(layer_p["moe"], h, cfg)
    else:
        y = mlp(layer_p["mlp"], h)
    return x + y, aux


def _embed_inputs(params, batch: Dict[str, Any], cfg):
    """Token embeddings, with stub-frontend embeddings prepended for vlm."""
    x = embed(params["embed"], batch["tokens"])
    if cfg.frontend == "vision_stub" and cfg.num_frontend_tokens > 0:
        fe = batch["frontend_embeds"].astype(x.dtype)  # (B, n_front, D) precomputed
        x = jnp.concatenate([fe, x], axis=1)
    return constrain_batch(x)


def _logical_positions(cfg, seq: int):
    return jnp.arange(seq)


# -------------------------------------------------------------------- forward
def decoder_forward(params, batch, cfg):
    """Teacher-forced forward. Returns (logits over token positions, aux)."""
    x = _embed_inputs(params, batch, cfg)
    B, S, D = x.shape
    positions = _logical_positions(cfg, S)
    flags = jnp.asarray(cfg.is_global_layer_flags())

    def body(x, xs):
        layer_p, is_global = xs
        x, aux = _layer_forward(layer_p, x, cfg, is_global, positions)
        return constrain_batch(x), aux

    fn = jax.checkpoint(body) if cfg.remat else body
    x, auxs = jax.lax.scan(fn, x, (params["layers"], flags))
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    n_front = cfg.num_frontend_tokens if cfg.frontend == "vision_stub" else 0
    if n_front:
        x = x[:, n_front:]
    logits = _unembed(params, x, cfg)
    return logits, jnp.sum(auxs)


def _unembed(params, x, cfg):
    from .common import output_head
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return unembed(params["embed"], x, cfg.vocab_size)
        return output_head(params["head"], x, cfg.vocab_size)


# -------------------------------------------------------------------- prefill
def decoder_prefill(params, batch, cfg, max_seq: int | None = None):
    """Run the prompt; return (last-token logits, cache). Cache KV buffers are
    sized ``max_seq`` (>= prompt length) so decode can append in place."""
    x = _embed_inputs(params, batch, cfg)
    B, S, D = x.shape
    max_seq = max(max_seq or S, S)
    positions = _logical_positions(cfg, S)
    flags = jnp.asarray(cfg.is_global_layer_flags())

    if cfg.family == "ssm":
        def body(x, layer_p):
            h = rmsnorm(layer_p["ln"], x, cfg.norm_eps)
            di, N = cfg.d_inner, cfg.ssm_state
            # re-run projection pieces to extract final state/conv tail
            y, state, tail = _ssm_prefill_layer(layer_p["ssm"], h, cfg)
            return constrain_batch(x + y), (state, tail)
        x, (h_states, tails) = jax.lax.scan(body, x, params["layers"])
        cache = {"ssm_h": h_states, "ssm_conv": tails, "pos": jnp.array(S, jnp.int32)}
    else:
        def body(x, xs):
            layer_p, is_global = xs
            h = rmsnorm(layer_p["ln1"], x, cfg.norm_eps)
            o, (k, v) = attn.prefill_attention(layer_p["attn"], h, cfg,
                                               is_global=is_global,
                                               positions=positions)
            x = x + o
            h = rmsnorm(layer_p["ln2"], x, cfg.norm_eps)
            if cfg.n_experts > 0:
                y, _ = moe_lib.moe_forward(layer_p["moe"], h, cfg)
            else:
                y = mlp(layer_p["mlp"], h)
            pad = max_seq - k.shape[1]
            if pad:
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            return constrain_batch(x + y), (k, v)
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], flags))
        cache = {"k": ks, "v": vs, "pos": jnp.array(S, jnp.int32)}

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x[:, -1:], cfg)[:, 0]
    return logits, cache


def _ssm_prefill_layer(p, h, cfg):
    """SSD forward that also returns (final_state, conv_tail) for decoding."""
    Bsz, S, D = h.shape
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    zxbcdt = h @ p["w_in"]
    z = zxbcdt[..., :di]
    xBC_raw = zxbcdt[..., di:di + di + 2 * N]
    dt_raw = zxbcdt[..., di + di + 2 * N:]
    xBC = ssm_lib._causal_conv(xBC_raw, p["conv_w"])
    xs = xBC[..., :di].reshape(Bsz, S, H, P)
    Bmat = xBC[..., di:di + N]
    Cmat = xBC[..., di + N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    pad = (-S) % cfg.ssm_chunk
    if pad:
        xs_p = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_p = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        C_p = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
    else:
        xs_p, dt_p, B_p, C_p = xs, dt, Bmat, Cmat
    y, final_state = ssm_lib.ssd_chunked(xs_p, dt_p, A, B_p, C_p, cfg.ssm_chunk,
                                         use_pallas=cfg.use_pallas)
    y = y[:, :S] + xs * p["D"][None, None, :, None].astype(h.dtype)
    y = y.reshape(Bsz, S, di)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    cw = cfg.ssm_conv_width
    tail = xBC_raw[:, -(cw - 1):, :]
    if S < cw - 1:  # tiny prompts: left-pad
        tail = jnp.pad(xBC_raw, ((0, 0), (cw - 1 - S, 0), (0, 0)))
    return y @ p["w_out"], final_state, tail


# --------------------------------------------------------------------- decode
def init_paged_decode_cache(cfg, num_pages: int, page_size: int):
    """Paged KV cache (continuous-batching serving): a shared page pool per
    attention layer. Slot bookkeeping (page table, seq lens) lives with the
    serving engine's allocator, not in the cache pytree. Attention-free
    stacks (family="ssm") get zero-layer pools — their serving state lives
    entirely in the recurrent-state pool."""
    if not cfg.supports_paged_kv:
        raise ValueError(f"{cfg.name}: no paged serving path "
                         f"({cfg.paged_unsupported_reason})")
    n_attn = 0 if cfg.family == "ssm" else cfg.n_layers
    kv = attn.init_paged_kv_cache(cfg, num_pages, page_size, n_attn)
    return {"k_pages": kv["k_pages"], "v_pages": kv["v_pages"]}


def init_decoder_recurrent_state(cfg, n_rows: int):
    """Recurrent-state slabs for the ssm family's serving slots: SSD state
    ``h`` (n_rows, L, H, P, N) fp32 and raw conv-tail ``conv``
    (n_rows, L, cw-1, di+2N). Row 0 is the pool's reserved scratch row
    (packed-prefill padding rows read/write it); slot ``s`` owns row
    ``s + 1`` (see serving.cache.RecurrentStatePool)."""
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    cw, di = cfg.ssm_conv_width, cfg.d_inner
    L = cfg.n_layers
    return {
        "h": jnp.zeros((n_rows, L, H, P, N), jnp.float32),
        "conv": jnp.zeros((n_rows, L, cw - 1, di + 2 * N), dtype_of(cfg)),
    }


def _attn_layer_runs(cfg):
    """Maximal runs of consecutive layers sharing one sliding window:
    [(window, first_layer, n_layers), ...] in stack order. Uniform stacks
    (all-global, or every layer the same window) collapse to one run, so
    the paged step keeps its single layer-scan; gemma3-style mixed stacks
    get one scan per run, each with its own static kernel ``window`` —
    which is what lets window runs also take a late ``pages_start``."""
    runs: list = []
    for i in range(cfg.n_layers):
        w = cfg.layer_window(i)
        if runs and runs[-1][0] == w:
            runs[-1][2] += 1
        else:
            runs.append([w, i, 1])
    return [tuple(r) for r in runs]


def decoder_prefill_paged_chunk(params, cache, tokens, page_table, start,
                                n_new, cfg, pages_bound=None,
                                window_start=0, state_rows=None):
    """One chunked-prefill step over the paged pool (continuous batching).

    tokens: (B, C) int32 — a fixed-width chunk of prompt tokens per serving
    slot, PAD-filled past ``n_new[b]``; page_table (B, MP) rows already
    cover positions ``start .. start + n_new - 1`` (the engine extends the
    slot's pages before calling). Each attention layer writes the chunk's
    K/V directly into the pool and attends causally to resident context +
    in-chunk keys (models.attention.paged_prefill_attention); sliding-window
    runs use their static per-layer window and may start their page walk at
    ``window_start`` (static, engine-bucketed). The ssm family instead
    advances per-slot recurrent state: ``cache["rec"]`` rows are gathered by
    ``state_rows`` (B,) int32 (0 = the scratch row padding rows use), a row
    whose chunk starts at position 0 re-enters from zero state (slot reuse
    needs no host-side reset), and the advanced rows scatter back. Returns
    (x_last (B, 1, D), cache with updated pools) — the final-norm hidden
    state of token ``start + n_new - 1``. The LM head is deliberately NOT
    applied here: only the final chunk's logits are ever consumed (they
    sample the first generated token), and the vocab projection is the
    widest matmul in the step — the engine applies ``ModelBundle.lm_head``
    host-side exactly once per prompt. ``pages_bound``: static live bound on
    the attention page walk (None = full static width)."""
    B, C = tokens.shape
    x = embed(params["embed"], tokens)

    if cfg.family == "ssm":
        rec = cache["rec"]
        fresh = (start == 0)
        # first chunk of a prompt starts from zero state, whatever the
        # previous tenant of the slot left behind
        h0 = jnp.where(fresh[:, None, None, None, None], 0.0,
                       rec["h"][state_rows])                 # (B, L, ...)
        tails = jnp.where(fresh[:, None, None, None], 0.0,
                          rec["conv"][state_rows]).astype(rec["conv"].dtype)

        def body(x, xs):
            layer_p, h_st, tail = xs
            h = rmsnorm(layer_p["ln"], x, cfg.norm_eps)
            y, h_new, tail_new = ssm_lib.ssm_prefill_chunk(
                layer_p["ssm"], h, h_st, tail, n_new, cfg)
            return constrain_batch(x + y), (h_new, tail_new)

        x, (h_new, tails_new) = jax.lax.scan(
            body, x, (params["layers"], jnp.moveaxis(h0, 0, 1),
                      jnp.moveaxis(tails, 0, 1)))
        rec = {"h": rec["h"].at[state_rows].set(jnp.moveaxis(h_new, 0, 1)),
               "conv": rec["conv"].at[state_rows].set(
                   jnp.moveaxis(tails_new, 0, 1))}
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        last = jnp.clip(n_new - 1, 0, C - 1)
        x_last = x[jnp.arange(B), last][:, None]              # (B, 1, D)
        return x_last, {**cache, "rec": rec}

    x, kps, vps = _paged_chunk_attn_hidden(params, cache, x, page_table,
                                           start, n_new, cfg, pages_bound,
                                           window_start)
    last = jnp.clip(n_new - 1, 0, C - 1)
    x_last = x[jnp.arange(B), last][:, None]                  # (B, 1, D)
    return x_last, {**cache, "k_pages": kps, "v_pages": vps}


def _scan_attn_runs(make_attn, params, cache, x, cfg, window_start):
    """Run every same-window layer run over ``x`` — attention (``make_attn
    (window, pages_start)`` returns the layer's paged attention call) then
    the FFN — with the whole (L, P, K, ps, Dh) pools in the scan carry:
    each layer writes its K/V into its own layer of the pools in place and
    its kernel reads that layer there, so no run slices the pools out or
    concatenates them back (at gemma3-4b serving widths that would hold
    three copies of a 2.3 GB pool beside 7.8 GB of weights). Returns (x,
    k_pages, v_pages)."""
    def make_body(window):
        attend = make_attn(window, window_start if window else 0)

        def body(carry, li):
            x, kp, vp = carry
            # index the whole stack in the loop: slicing each run's weights
            # out before its scan would copy them
            layer_p = jax.tree_util.tree_map(lambda a: a[li],
                                             params["layers"])
            h = rmsnorm(layer_p["ln1"], x, cfg.norm_eps)
            o, kp, vp = attend(layer_p["attn"], h, kp, vp, li)
            x = x + o
            h = rmsnorm(layer_p["ln2"], x, cfg.norm_eps)
            if cfg.n_experts > 0:
                y, _ = moe_lib.moe_forward(layer_p["moe"], h, cfg)
            else:
                y = mlp(layer_p["mlp"], h)
            return (constrain_batch(x + y), kp, vp), None
        return body

    kp, vp = cache["k_pages"], cache["v_pages"]
    for w, i0, n in _attn_layer_runs(cfg):
        (x, kp, vp), _ = jax.lax.scan(
            make_body(w), (x, kp, vp),
            jnp.arange(i0, i0 + n, dtype=jnp.int32))
    return x, kp, vp


def _paged_chunk_attn_hidden(params, cache, x, page_table, start, n_new, cfg,
                             pages_bound, window_start):
    """Shared attention-family chunk body: run every layer over the
    embedded chunk ``x`` (B, C, D) — each layer writing the chunk's K/V
    straight into the pool pages and attending causally to resident
    context + in-chunk keys — then the final norm. Returns (x (B, C, D)
    post-norm hidden states for EVERY chunk position, kps, vps).
    ``decoder_prefill_paged_chunk`` keeps only the carry position;
    ``decoder_verify_paged_chunk`` returns all of them."""
    def make_attn(window, pages_start):
        return lambda p, h, kp, vp, li: attn.paged_prefill_attention(
            p, h, kp, vp, li, page_table, start, n_new, cfg, pages_bound,
            window=window, pages_start=pages_start)

    x, kps, vps = _scan_attn_runs(make_attn, params, cache, x, cfg,
                                  window_start)
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return x, kps, vps


def decoder_verify_paged_chunk(params, cache, tokens, page_table, start,
                               n_new, cfg, pages_bound=None, window_start=0):
    """Speculative-verify chunk: the same compute as the chunked paged
    prefill — the chunk's K/V land in the pool pages, every position
    attends causally to resident context + in-chunk keys — but returning
    the FULL post-norm hidden states (B, C, D) instead of just the carry.
    Row c scores the model's next-token distribution after token
    ``start + c`` (apply ``ModelBundle.lm_head`` for (B, C, V) logits),
    which is exactly the shape verifying a γ-token draft chunk needs: one
    launch replaces γ+1 sequential decode steps. Positions past
    ``n_new[b]`` are PAD garbage the caller must ignore.

    Only rollback-capable stacks verify: a rejected suffix is undone by
    ``PagedKVCache.truncate_slot`` (pages freed, ``seq_lens`` rewound),
    which has no analogue for recurrent state — SSM/hybrid stacks (and,
    by engine policy, sliding-window stacks) serve non-speculatively and
    keep ``ModelBundle.verify_paged_chunk = None``."""
    if cfg.family == "ssm":
        raise ValueError(f"{cfg.name}: recurrent state cannot roll back a "
                         "rejected draft suffix; ssm stacks do not verify")
    x = embed(params["embed"], tokens)
    x, kps, vps = _paged_chunk_attn_hidden(params, cache, x, page_table,
                                           start, n_new, cfg, pages_bound,
                                           window_start)
    return x, {**cache, "k_pages": kps, "v_pages": vps}


def decoder_decode_step_paged(params, cache, token, page_table, seq_lens,
                              active, cfg, pages_bound=None, window_start=0):
    """One continuous-batching decode step over the serving slots.

    token: (B, 1) int32 — per-slot next token; page_table (B, MP),
    seq_lens (B,) int32, active (B,) bool come from the engine's page
    allocator; ``pages_bound`` is the engine's static live page bound (None
    = full static width) and ``window_start`` the static first page of
    sliding-window runs' walks (global runs always walk from page 0). The
    ssm family advances ``cache["rec"]`` rows 1..B instead (row 0 is the
    scratch row); rows of slots not in ``active`` keep their state
    unchanged, so a decode dispatch can never corrupt a slot that is still
    mid-prefill. Returns (logits (B, V), cache with updated pools)."""
    x = embed(params["embed"], token)

    if cfg.family == "ssm":
        rec = cache["rec"]
        act = active.reshape(-1)

        def body(x, xs):
            layer_p, h_st, tail = xs
            hn = rmsnorm(layer_p["ln"], x, cfg.norm_eps)
            y, h_new, tail_new = ssm_lib.ssm_decode_step(layer_p["ssm"], hn,
                                                         h_st, tail, cfg)
            h_new = jnp.where(act[:, None, None, None], h_new, h_st)
            tail_new = jnp.where(act[:, None, None], tail_new, tail)
            return constrain_batch(x + y), (h_new, tail_new)

        x, (h_new, tails_new) = jax.lax.scan(
            body, x, (params["layers"], jnp.moveaxis(rec["h"][1:], 0, 1),
                      jnp.moveaxis(rec["conv"][1:], 0, 1)))
        rec = {"h": rec["h"].at[1:].set(jnp.moveaxis(h_new, 0, 1)),
               "conv": rec["conv"].at[1:].set(
                   jnp.moveaxis(tails_new, 0, 1))}
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = _unembed(params, x, cfg)[:, 0]
        return logits, {**cache, "rec": rec}

    def make_attn(window, pages_start):
        return lambda p, h, kp, vp, li: attn.paged_decode_attention(
            p, h, kp, vp, li, page_table, seq_lens, active, cfg, pages_bound,
            window=window, pages_start=pages_start)

    x, kps, vps = _scan_attn_runs(make_attn, params, cache, x, cfg,
                                  window_start)
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)[:, 0]
    return logits, {**cache, "k_pages": kps, "v_pages": vps}


def init_decode_cache(cfg, batch: int, max_seq: int):
    if cfg.family == "ssm":
        st = ssm_lib.init_ssm_state(cfg, batch, cfg.n_layers)
        return {"ssm_h": st["h"], "ssm_conv": st["conv"],
                "pos": jnp.array(0, jnp.int32)}
    kv = attn.init_kv_cache(cfg, batch, max_seq, cfg.n_layers)
    return {"k": kv["k"], "v": kv["v"], "pos": jnp.array(0, jnp.int32)}


def decoder_decode_step(params, cache, token, cfg, *, windowed=False):
    """One decode step. token: (B, 1) int32. Returns (logits (B, V), cache)."""
    pos = cache["pos"]
    x = embed(params["embed"], token)
    flags = jnp.asarray(cfg.is_global_layer_flags())

    if cfg.family == "ssm":
        def body(x, xs):
            layer_p, h_st, tail = xs
            hn = rmsnorm(layer_p["ln"], x, cfg.norm_eps)
            y, h_new, tail_new = ssm_lib.ssm_decode_step(layer_p["ssm"], hn,
                                                         h_st, tail, cfg)
            return constrain_batch(x + y), (h_new, tail_new)
        x, (h_new, tails_new) = jax.lax.scan(
            body, x, (params["layers"], cache["ssm_h"], cache["ssm_conv"]))
        new_cache = {"ssm_h": h_new, "ssm_conv": tails_new, "pos": pos + 1}
    else:
        # all-global stacks keep a STATIC flag so the flash-decode
        # (shard_map) fast path can engage; mixed local/global stacks
        # (gemma3) scan the per-layer flag.
        uniform_global = all(cfg.is_global_layer_flags())

        def body(x, xs):
            layer_p, lk, lv, is_global = xs
            if uniform_global:
                is_global = True
            h = rmsnorm(layer_p["ln1"], x, cfg.norm_eps)
            o, lk, lv = attn.decode_attention(layer_p["attn"], h, lk, lv, pos,
                                              cfg, is_global=is_global,
                                              windowed=windowed)
            x = x + o
            h = rmsnorm(layer_p["ln2"], x, cfg.norm_eps)
            if cfg.n_experts > 0:
                y, _ = moe_lib.moe_forward(layer_p["moe"], h, cfg)
            else:
                y = mlp(layer_p["mlp"], h)
            return constrain_batch(x + y), (lk, lv)
        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], flags))
        new_cache = {"k": ks, "v": vs, "pos": pos + 1}

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)[:, 0]
    return logits, new_cache
