"""Tier kind ``decoder``: a dense pre-norm decoder (Mistral's block).

The plain float32 reference of the architecture, the work of one token
through its layers, and how many of its layers keep K/V in the paged cache
(see ``chipbench.spec.tier_kind``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from chipbench import work
from chipbench.reference import (HI, SEQ_BLOCK, _pad_seqs, head, linear,
                                 rmsnorm, rope, swiglu)

Q_BLOCK = 256        # query rows per attention block


@functools.partial(jax.jit, static_argnames=("i", "heads", "kv_heads",
                                             "theta", "eps", "quant"))
def _decoder_layer(layers, x, i, heads, kv_heads, theta, eps, quant):
    """One pre-norm block: x + attn(norm(x)); x + swiglu(norm(x))."""
    p = jax.tree_util.tree_map(lambda a: a[i], layers)
    S, D = x.shape
    h = rmsnorm(x, p["ln1"]["scale"], eps)
    Dh = p["attn"]["wq"].shape[-1]
    q = linear(h, p["attn"]["wq"].reshape(D, heads * Dh), quant)
    k = linear(h, p["attn"]["wk"].reshape(D, kv_heads * Dh), quant)
    v = linear(h, p["attn"]["wv"].reshape(D, kv_heads * Dh), quant)
    pos = jnp.arange(S)
    q = rope(q.reshape(S, heads, Dh), pos, theta) * Dh ** -0.5
    k = rope(k.reshape(S, kv_heads, Dh), pos, theta)
    v = v.reshape(S, kv_heads, Dh)
    G = heads // kv_heads
    kk = jnp.repeat(k, G, axis=1)          # query head h reads KV head h//G
    vv = jnp.repeat(v, G, axis=1)

    def block(qs):
        q_i, q_pos = qs
        s = jnp.einsum("qhd,khd->hqk", q_i, kk, precision=HI)
        s = jnp.where(pos[None, None, :] <= q_pos[None, :, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, vv, precision=HI)

    nb = S // Q_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, heads, Dh),
                            pos.reshape(nb, Q_BLOCK)))
    x = x + linear(o.reshape(S, heads * Dh), p["attn"]["wo"], quant)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    return x + swiglu(h, p["mlp"], quant)


class Reference:
    """A dense pre-norm decoder: RMSNorm, RoPE, grouped-query attention,
    SwiGLU, untied LM head (Mistral's block)."""

    def __init__(self, params, model: dict, quant: Optional[str] = None):
        self.p, self.m, self.quant = params, model, quant

    def gaps(self, seqs, rows, tokens) -> list:
        """For each token sequence of ``seqs``, at its positions
        ``rows[b]`` (row r predicts token r + 1): how far the logit of
        ``tokens[b]`` lies below the best one, the best token and the best
        logit (``head``). One sequence at a time: the attention of a long one
        fills the chip."""
        m = self.m
        out = []
        for seq, r, t in zip(seqs, rows, tokens):
            toks = jnp.asarray(_pad_seqs([seq], SEQ_BLOCK)[0])
            x = self.p["embed"]["table"][toks].astype(jnp.float32)
            for i in range(m["n_layers"]):
                x = _decoder_layer(self.p["layers"], x, i, m["n_heads"],
                                   m["n_kv_heads"], float(m["rope_theta"]),
                                   float(m["norm_eps"]), self.quant)
            out += head(self.p, m, x[None], [r], [t], self.quant)
        return out


def token_flops(m: dict, keys: float) -> float:
    """One token through the layers (not the LM head): matmuls, plus
    attention over ``keys`` keys."""
    return 2.0 * work.decoder_layer_params(m) * m["n_layers"] \
        + work.attention_flops(m, keys)


def paged_layers(m: dict) -> int:
    """Every layer keeps its K/V in the paged cache."""
    return m["n_layers"]
