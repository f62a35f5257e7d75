"""BERT-style bidirectional encoder — the router backbone (paper §3).

The paper uses DeBERTa-v3-large (300M). We implement a BERT-class encoder
with T5-style relative-position attention bias (a light-weight stand-in for
DeBERTa's disentangled relative attention, which is the architecturally
relevant ingredient), mean-pooling over non-pad tokens, and a 2-layer scoring
head producing a single logit; ``sigmoid(logit) = p_w(x) ∈ [0, 1]``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .common import dense_init, init_mlp, init_rmsnorm, mlp, rmsnorm


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    vocab_size: int
    n_layers: int = 4
    d_model: int = 128
    n_heads: int = 4
    d_ff: int = 512
    max_seq: int = 256
    rel_buckets: int = 32
    rel_max_distance: int = 128
    norm_eps: float = 1e-6
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def _relative_bucket(rel: jnp.ndarray, n_buckets: int, max_dist: int) -> jnp.ndarray:
    """T5 symmetric relative position bucketing."""
    n = n_buckets // 2
    ret = jnp.where(rel > 0, n, 0)
    rel = jnp.abs(rel)
    max_exact = n // 2
    is_small = rel < max_exact
    log_ratio = jnp.log(rel.astype(jnp.float32) / max_exact + 1e-6) \
        / jnp.log(max_dist / max_exact)
    large = max_exact + (log_ratio * (n - max_exact)).astype(jnp.int32)
    large = jnp.minimum(large, n - 1)
    return ret + jnp.where(is_small, rel, large)


def init_router_encoder(key, cfg: RouterConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5 + cfg.n_layers * 3)
    layers = []
    for i in range(cfg.n_layers):
        k1, k2, k3 = ks[5 + 3 * i:8 + 3 * i]
        layers.append({
            "ln1": init_rmsnorm(cfg.d_model, dt),
            "wqkv": dense_init(k1, cfg.d_model, (3, cfg.n_heads, cfg.head_dim), dt),
            "wo": dense_init(k2, cfg.d_model, cfg.d_model, dt),
            "ln2": init_rmsnorm(cfg.d_model, dt),
            "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, dt),
        })
    from .common import stack_params
    return {
        "embed": (jax.random.truncated_normal(ks[0], -2., 2.,
                                              (cfg.vocab_size, cfg.d_model)) * 0.02
                  ).astype(dt),
        "rel_bias": (jax.random.normal(ks[1], (cfg.rel_buckets, cfg.n_heads)) * 0.02
                     ).astype(dt),
        "layers": stack_params(layers),
        "ln_f": init_rmsnorm(cfg.d_model, dt),
        "head_w1": dense_init(ks[2], cfg.d_model, cfg.d_model, dt),
        "head_w2": dense_init(ks[3], cfg.d_model, 1, dt),
    }


def router_encode(params, tokens, mask, cfg: RouterConfig) -> jnp.ndarray:
    """tokens: (B, S) int32; mask: (B, S) 1=real token. Returns logits (B,)."""
    with jax.named_scope("router_encode"):
        B, S = tokens.shape
        x = params["embed"][tokens]
        pos = jnp.arange(S)
        rel = pos[None, :] - pos[:, None]
        buckets = _relative_bucket(rel, cfg.rel_buckets, cfg.rel_max_distance)
        bias = params["rel_bias"][buckets]              # (S, S, H)
        bias = jnp.transpose(bias, (2, 0, 1))[None]     # (1, H, S, S)
        attn_mask = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e30)
        scale = cfg.head_dim ** -0.5

        def body(x, layer_p):
            h = rmsnorm(layer_p["ln1"], x, cfg.norm_eps)
            qkv = jnp.einsum("bsd,dthk->tbshk", h, layer_p["wqkv"])
            q, k, v = qkv[0], qkv[1], qkv[2]
            scores = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
            scores = scores + bias.astype(jnp.float32) + attn_mask
            w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            o = jnp.einsum("bhqs,bshk->bqhk", w, v).reshape(B, S, cfg.d_model)
            x = x + o @ layer_p["wo"]
            h = rmsnorm(layer_p["ln2"], x, cfg.norm_eps)
            return x + mlp(layer_p["mlp"], h), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        denom = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
        pooled = (x * mask[..., None].astype(x.dtype)).sum(1) / denom.astype(x.dtype)
        h = jnp.tanh(pooled @ params["head_w1"])
        return (h @ params["head_w2"])[:, 0].astype(jnp.float32)


def router_score(params, tokens, mask, cfg: RouterConfig) -> jnp.ndarray:
    """p_w(x) ∈ [0,1] — the paper's router score."""
    return jax.nn.sigmoid(router_encode(params, tokens, mask, cfg))
