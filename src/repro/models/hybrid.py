"""Jamba-style hybrid Mamba/attention architecture (arXiv:2403.19887).

Layout: blocks of ``attn_every`` (=8) layers, one attention layer per block at
``attn_offset`` (=4), the rest SSD (Mamba) mixers; the FFN alternates
dense / MoE every ``moe_every`` (=2) layers. The stack scans over *blocks*
(intra-block pattern unrolled) so params stay homogeneous per block.

Adaptation note (DESIGN.md): Jamba uses Mamba-1 internally; we use the same
SSD (Mamba-2) mixer as the ssm family — state-space layer of equivalent role,
TPU-friendlier (chunked matmuls hit the MXU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention as attn
from . import moe as moe_lib
from . import ssm as ssm_lib
from .common import (dtype_of, embed, init_embedding, init_mlp, init_rmsnorm,
                     mlp, rmsnorm, stack_params)
from .decoder import _unembed
from repro.sharding.context import constrain_batch


def _block_layout(cfg):
    """Per-position (mixer, ffn) kinds within one block."""
    pos = []
    for j in range(cfg.attn_every):
        mixer = "attn" if j == cfg.attn_offset else "mamba"
        ffn = "moe" if (cfg.moe_every and j % cfg.moe_every == 1) else "mlp"
        pos.append((mixer, ffn))
    return pos


def init_block(key, cfg) -> dict:
    layout = _block_layout(cfg)
    dt = dtype_of(cfg)
    n_mamba = sum(1 for m, _ in layout if m == "mamba")
    n_moe = sum(1 for _, f in layout if f == "moe")
    n_mlp = len(layout) - n_moe
    ks = iter(jax.random.split(key, n_mamba + n_moe + n_mlp + 1))
    mamba = stack_params([
        {"ln": init_rmsnorm(cfg.d_model, dt), "ssm": ssm_lib.init_ssm(next(ks), cfg)}
        for _ in range(n_mamba)])
    attn_p = {"ln1": init_rmsnorm(cfg.d_model, dt),
              "attn": attn.init_attention(next(ks), cfg)}
    moe_p = stack_params([
        {"ln2": init_rmsnorm(cfg.d_model, dt), "moe": moe_lib.init_moe(next(ks), cfg)}
        for _ in range(n_moe)])
    mlp_p = stack_params([
        {"ln2": init_rmsnorm(cfg.d_model, dt),
         "mlp": init_mlp(next(ks), cfg.d_model, cfg.d_ff, dt)}
        for _ in range(n_mlp)])
    return {"mamba": mamba, "attn": attn_p, "moe": moe_p, "mlp": mlp_p}


def init_hybrid(key, cfg) -> dict:
    n_blocks = cfg.n_layers // cfg.attn_every
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    blocks = stack_params([init_block(k, cfg)
                           for k in jax.random.split(k_blocks, n_blocks)])
    p = {"embed": init_embedding(k_emb, cfg), "blocks": blocks,
         "ln_f": init_rmsnorm(cfg.d_model, dtype_of(cfg))}
    if not cfg.tie_embeddings:
        from .common import init_output_head
        p["head"] = init_output_head(k_head, cfg)
    return p


def _take(tree, i: int):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _apply_ffn(block_p, x, j_moe, j_mlp, is_moe, cfg):
    if is_moe:
        p = _take(block_p["moe"], j_moe)
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        y, aux = moe_lib.moe_forward(p["moe"], h, cfg)
    else:
        p = _take(block_p["mlp"], j_mlp)
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        y, aux = mlp(p["mlp"], h), jnp.zeros((), jnp.float32)
    return x + y, aux


# -------------------------------------------------------------------- forward
def hybrid_forward(params, batch, cfg):
    x = embed(params["embed"], batch["tokens"])
    B, S, D = x.shape
    positions = jnp.arange(S)
    layout = _block_layout(cfg)

    def sublayer(x, block_p, idx):
        mixer, ffn = layout[idx]
        jm = sum(1 for m, _ in layout[:idx] if m == "mamba")
        jmoe = sum(1 for _, f in layout[:idx] if f == "moe")
        jmlp = idx - jmoe
        if mixer == "attn":
            h = rmsnorm(block_p["attn"]["ln1"], x, cfg.norm_eps)
            x = x + attn.attention_forward(block_p["attn"]["attn"], h, cfg,
                                           positions=positions)
        else:
            p = _take(block_p["mamba"], jm)
            h = rmsnorm(p["ln"], x, cfg.norm_eps)
            x = x + ssm_lib.ssm_forward(p["ssm"], h, cfg)
        x, aux = _apply_ffn(block_p, x, jmoe, jmlp, ffn == "moe", cfg)
        return constrain_batch(x), aux

    def block_fn(x, block_p):
        # nested remat: checkpoint each (mixer + ffn) sub-layer so the
        # backward pass keeps only one sub-layer's intermediates live at a
        # time (blocks are 8 layers deep — §Perf jamba iteration).
        aux_total = jnp.zeros((), jnp.float32)
        for idx in range(len(layout)):
            f = (jax.checkpoint(lambda x, bp, i=idx: sublayer(x, bp, i))
                 if cfg.remat else (lambda x, bp, i=idx: sublayer(x, bp, i)))
            x, aux = f(x, block_p)
            aux_total = aux_total + aux
        return x, aux_total

    fn = jax.checkpoint(block_fn) if cfg.remat else block_fn
    x, auxs = jax.lax.scan(fn, x, params["blocks"])
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return _unembed(params, x, cfg), jnp.sum(auxs)


# -------------------------------------------------------------------- prefill
def hybrid_prefill(params, batch, cfg, max_seq: int | None = None):
    from .decoder import _ssm_prefill_layer
    x = embed(params["embed"], batch["tokens"])
    B, S, D = x.shape
    max_seq = max(max_seq or S, S)
    positions = jnp.arange(S)
    layout = _block_layout(cfg)

    def block_fn(x, block_p):
        jm = jmoe = jmlp = 0
        states, tails = [], []
        kv = None
        for (mixer, ffn) in layout:
            if mixer == "attn":
                h = rmsnorm(block_p["attn"]["ln1"], x, cfg.norm_eps)
                o, (k, v) = attn.prefill_attention(block_p["attn"]["attn"], h,
                                                   cfg, positions=positions)
                pad = max_seq - k.shape[1]
                if pad:
                    k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                kv = (k, v)
                x = x + o
            else:
                p = _take(block_p["mamba"], jm)
                jm += 1
                h = rmsnorm(p["ln"], x, cfg.norm_eps)
                y, st, tail = _ssm_prefill_layer(p["ssm"], h, cfg)
                states.append(st)
                tails.append(tail)
                x = x + y
            x, _ = _apply_ffn(block_p, x, jmoe, jmlp, ffn == "moe", cfg)
            if ffn == "moe":
                jmoe += 1
            else:
                jmlp += 1
        return constrain_batch(x), (kv[0], kv[1], jnp.stack(states), jnp.stack(tails))

    x, (ks, vs, states, tails) = jax.lax.scan(block_fn, x, params["blocks"])
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x[:, -1:], cfg)[:, 0]
    cache = {"k": ks, "v": vs, "ssm_h": states, "ssm_conv": tails,
             "pos": jnp.array(S, jnp.int32)}
    return logits, cache


# ------------------------------------------------------- paged (continuous)
def init_hybrid_paged_cache(cfg, num_pages: int, page_size: int):
    """Paged KV pool for the hybrid stack's attention layers (one per
    block): (n_blocks, num_pages, K, page_size, Dh) per tensor, page 0
    reserved as scratch. The Mamba layers' state lives in the recurrent
    pool (init_hybrid_recurrent_state), not here."""
    if not cfg.supports_paged_kv:
        raise ValueError(f"{cfg.name}: no paged serving path "
                         f"({cfg.paged_unsupported_reason})")
    n_blocks = cfg.n_layers // cfg.attn_every
    kv = attn.init_paged_kv_cache(cfg, num_pages, page_size, n_blocks)
    return {"k_pages": kv["k_pages"], "v_pages": kv["v_pages"]}


def init_hybrid_recurrent_state(cfg, n_rows: int):
    """Recurrent-state slabs for the hybrid stack's serving slots: SSD
    state ``h`` (n_rows, n_blocks, n_mamba, H, P, N) fp32 and raw conv-tail
    ``conv`` (n_rows, n_blocks, n_mamba, cw-1, di+2N). Row 0 is the
    reserved scratch row; slot ``s`` owns row ``s + 1`` (see
    serving.cache.RecurrentStatePool)."""
    n_blocks = cfg.n_layers // cfg.attn_every
    n_mamba = sum(1 for m, _ in _block_layout(cfg) if m == "mamba")
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    cw, di = cfg.ssm_conv_width, cfg.d_inner
    return {
        "h": jnp.zeros((n_rows, n_blocks, n_mamba, H, P, N), jnp.float32),
        "conv": jnp.zeros((n_rows, n_blocks, n_mamba, cw - 1, di + 2 * N),
                          dtype_of(cfg)),
    }


def hybrid_prefill_paged_chunk(params, cache, tokens, page_table, start,
                               n_new, cfg, pages_bound=None, window_start=0,
                               state_rows=None):
    """One chunked-prefill step of the hybrid stack (continuous batching).

    tokens: (B, C) int32 chunk per serving slot, PAD-filled past
    ``n_new[b]``; page_table (B, MP) rows already cover positions
    ``start .. start + n_new - 1``. Attention layers write the chunk's K/V
    into the pool and attend causally by global position
    (models.attention.paged_prefill_attention); Mamba layers advance the
    gathered ``cache["rec"]`` rows (``state_rows`` (B,) int32; 0 = scratch
    row for padding rows) through ``ssm_lib.ssm_prefill_chunk`` — a row
    whose chunk starts at position 0 re-enters from zero state, so slot
    reuse needs no host-side reset. Returns (x_last (B, 1, D), cache); the
    LM head is applied by the engine only when a prompt finishes
    (ModelBundle.lm_head). ``pages_bound``/``window_start``: static page-walk
    bounds (hybrid attention layers are global, so ``window_start`` is
    unused but kept for signature parity)."""
    del window_start
    B, C = tokens.shape
    x = embed(params["embed"], tokens)
    layout = _block_layout(cfg)
    rec = cache["rec"]
    fresh = (start == 0)
    h0 = jnp.where(fresh[:, None, None, None, None, None], 0.0,
                   rec["h"][state_rows])          # (B, nb, nm, H, P, N)
    tails = jnp.where(fresh[:, None, None, None, None], 0.0,
                      rec["conv"][state_rows]).astype(rec["conv"].dtype)
    # scan over blocks: move the block axis in front of the batch axis
    h0 = jnp.moveaxis(h0, 0, 1)                   # (nb, B, nm, ...)
    tails = jnp.moveaxis(tails, 0, 1)

    def block_fn(carry, xs):
        x, kp, vp = carry                         # whole-stack pools
        block_p, bi, h_sts, tls = xs              # h_sts: (B, nm, ...)
        jm = jmoe = jmlp = 0
        new_states, new_tails = [], []
        for (mixer, ffn) in layout:
            if mixer == "attn":
                h = rmsnorm(block_p["attn"]["ln1"], x, cfg.norm_eps)
                o, kp, vp = attn.paged_prefill_attention(
                    block_p["attn"]["attn"], h, kp, vp, bi, page_table,
                    start, n_new, cfg, pages_bound)
                x = x + o
            else:
                p = _take(block_p["mamba"], jm)
                h = rmsnorm(p["ln"], x, cfg.norm_eps)
                y, h_new, tail_new = ssm_lib.ssm_prefill_chunk(
                    p["ssm"], h, h_sts[:, jm], tls[:, jm], n_new, cfg)
                new_states.append(h_new)
                new_tails.append(tail_new)
                x = x + y
                jm += 1
            x, _ = _apply_ffn(block_p, x, jmoe, jmlp, ffn == "moe", cfg)
            if ffn == "moe":
                jmoe += 1
            else:
                jmlp += 1
        return (constrain_batch(x), kp, vp), (jnp.stack(new_states, axis=1),
                                              jnp.stack(new_tails, axis=1))

    n_blocks = cache["k_pages"].shape[0]
    (x, kps, vps), (states, new_tails) = jax.lax.scan(
        block_fn, (x, cache["k_pages"], cache["v_pages"]),
        (params["blocks"], jnp.arange(n_blocks, dtype=jnp.int32), h0, tails))
    rec = {"h": rec["h"].at[state_rows].set(jnp.moveaxis(states, 0, 1)),
           "conv": rec["conv"].at[state_rows].set(
               jnp.moveaxis(new_tails, 0, 1))}
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    last = jnp.clip(n_new - 1, 0, C - 1)
    x_last = x[jnp.arange(B), last][:, None]                  # (B, 1, D)
    return x_last, {"k_pages": kps, "v_pages": vps, "rec": rec}


def hybrid_decode_step_paged(params, cache, token, page_table, seq_lens,
                             active, cfg, pages_bound=None, window_start=0):
    """One continuous-batching decode step of the hybrid stack.

    token: (B, 1) int32 per-slot next token; page_table (B, MP), seq_lens
    (B,), active (B,) bool from the engine's allocator. Attention layers
    run the paged decode kernel over the block's page pool; Mamba layers
    advance ``cache["rec"]`` rows 1..B (row 0 is scratch), and rows of
    slots not in ``active`` keep their state unchanged so a decode dispatch
    can never corrupt a mid-prefill slot. Returns (logits (B, V), cache)."""
    del window_start
    x = embed(params["embed"], token)
    layout = _block_layout(cfg)
    rec = cache["rec"]
    act = active.reshape(-1)
    h_all = jnp.moveaxis(rec["h"][1:], 0, 1)      # (nb, B, nm, ...)
    t_all = jnp.moveaxis(rec["conv"][1:], 0, 1)

    def block_fn(carry, xs):
        x, kp, vp = carry                         # whole-stack pools
        block_p, bi, h_sts, tls = xs
        jm = jmoe = jmlp = 0
        new_states, new_tails = [], []
        for (mixer, ffn) in layout:
            if mixer == "attn":
                h = rmsnorm(block_p["attn"]["ln1"], x, cfg.norm_eps)
                o, kp, vp = attn.paged_decode_attention(
                    block_p["attn"]["attn"], h, kp, vp, bi, page_table,
                    seq_lens, active, cfg, pages_bound)
                x = x + o
            else:
                p = _take(block_p["mamba"], jm)
                h = rmsnorm(p["ln"], x, cfg.norm_eps)
                y, h_new, tail_new = ssm_lib.ssm_decode_step(
                    p["ssm"], h, h_sts[:, jm], tls[:, jm], cfg)
                h_new = jnp.where(act[:, None, None, None], h_new,
                                  h_sts[:, jm])
                tail_new = jnp.where(act[:, None, None], tail_new,
                                     tls[:, jm])
                new_states.append(h_new)
                new_tails.append(tail_new)
                x = x + y
                jm += 1
            x, _ = _apply_ffn(block_p, x, jmoe, jmlp, ffn == "moe", cfg)
            if ffn == "moe":
                jmoe += 1
            else:
                jmlp += 1
        return (constrain_batch(x), kp, vp), (jnp.stack(new_states, axis=1),
                                              jnp.stack(new_tails, axis=1))

    n_blocks = cache["k_pages"].shape[0]
    (x, kps, vps), (states, tails) = jax.lax.scan(
        block_fn, (x, cache["k_pages"], cache["v_pages"]),
        (params["blocks"], jnp.arange(n_blocks, dtype=jnp.int32), h_all,
         t_all))
    rec = {"h": rec["h"].at[1:].set(jnp.moveaxis(states, 0, 1)),
           "conv": rec["conv"].at[1:].set(jnp.moveaxis(tails, 0, 1))}
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)[:, 0]
    return logits, {"k_pages": kps, "v_pages": vps, "rec": rec}


# --------------------------------------------------------------------- decode
def init_hybrid_cache(cfg, batch: int, max_seq: int):
    n_blocks = cfg.n_layers // cfg.attn_every
    n_mamba = sum(1 for m, _ in _block_layout(cfg) if m == "mamba")
    K, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    cw, di = cfg.ssm_conv_width, cfg.d_inner
    dt = dtype_of(cfg)
    return {
        "k": jnp.zeros((n_blocks, batch, max_seq, K, Dh), dt),
        "v": jnp.zeros((n_blocks, batch, max_seq, K, Dh), dt),
        "ssm_h": jnp.zeros((n_blocks, n_mamba, batch, H, P, N), jnp.float32),
        "ssm_conv": jnp.zeros((n_blocks, n_mamba, batch, cw - 1, di + 2 * N), dt),
        "pos": jnp.array(0, jnp.int32),
    }


def hybrid_decode_step(params, cache, token, cfg, *, windowed=False):
    pos = cache["pos"]
    x = embed(params["embed"], token)
    layout = _block_layout(cfg)

    def block_fn(x, xs):
        block_p, lk, lv, h_sts, tails = xs
        jm = jmoe = jmlp = 0
        new_states, new_tails = [], []
        for (mixer, ffn) in layout:
            if mixer == "attn":
                h = rmsnorm(block_p["attn"]["ln1"], x, cfg.norm_eps)
                o, lk, lv = attn.decode_attention(block_p["attn"]["attn"], h,
                                                  lk, lv, pos, cfg,
                                                  windowed=windowed)
                x = x + o
            else:
                p = _take(block_p["mamba"], jm)
                h = rmsnorm(p["ln"], x, cfg.norm_eps)
                y, h_new, tail_new = ssm_lib.ssm_decode_step(
                    p["ssm"], h, h_sts[jm], tails[jm], cfg)
                new_states.append(h_new)
                new_tails.append(tail_new)
                x = x + y
                jm += 1
            x, _ = _apply_ffn(block_p, x, jmoe, jmlp, ffn == "moe", cfg)
            if ffn == "moe":
                jmoe += 1
            else:
                jmlp += 1
        return constrain_batch(x), (lk, lv, jnp.stack(new_states), jnp.stack(new_tails))

    x, (ks, vs, states, tails) = jax.lax.scan(
        block_fn, x,
        (params["blocks"], cache["k"], cache["v"], cache["ssm_h"],
         cache["ssm_conv"]))
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = _unembed(params, x, cfg)[:, 0]
    return logits, {"k": ks, "v": vs, "ssm_h": states, "ssm_conv": tails,
                    "pos": pos + 1}
