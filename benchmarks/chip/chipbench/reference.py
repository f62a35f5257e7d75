"""The float32 pieces the plain references share, and the router's.

Each served architecture's reference is in its kind file
(``kinds/<kind>.py``) and is built from the pieces here. Each follows the
architecture's published description, written again in straightforward
``jax.numpy`` with ``precision="highest"`` matmuls: no kernel, cache,
batching or chunking of the program's. It imports nothing
of the program and reads only the weights the benchmark made. Departures
from the published models are the program's own, and are named in the
configuration files.

``quant="fp8"`` computes every linear layer on the fp8 (e4m3) grid
(weights per output channel, activations per token, symmetric): the
control that a correct comparison has to reject.

Sequences are padded at the end to a multiple of ``SEQ_BLOCK`` tokens, so
the references compile once per bucket; causal models ignore what follows
a position.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEQ_BLOCK = 1024     # sequence bucket of the served models' references
FFN_BLOCK = 4096     # FFN columns per matmul block (bounds float32 temps)


def _quantise(x, axis, quant: str):
    """Symmetric fake quantisation to fp8 (e4m3) along ``axis`` (the
    reduction axis of the matmul), scaled per row or column to the
    format's largest value: returns (values on the fp8 grid, as float32;
    scale)."""
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def linear(x, w, quant: Optional[str]):
    """x (S, in) float32 @ w (in, out) -> (S, out) float32; with ``quant``
    both operands on the fp8 grid (activations per token, weights per
    output channel)."""
    w = w.astype(jnp.float32)
    if quant is None:
        return jnp.dot(x, w, precision=HI)
    xq, sx = _quantise(x, 1, quant)
    wq, sw = _quantise(w, 0, quant)
    return jnp.dot(xq, wq, precision=HI) * sx * sw


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotary embedding on (S, heads, D), halves rotated (GPT-NeoX form)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(h, p, quant):
    """down(silu(h @ gate) * (h @ up)), in column blocks of the hidden."""
    F = p["w_in"].shape[1]
    cols = []
    for j in range(0, F, FFN_BLOCK):
        g = linear(h, p["w_gate"][:, j:j + FFN_BLOCK], quant)
        u = linear(h, p["w_in"][:, j:j + FFN_BLOCK], quant)
        cols.append(jax.nn.silu(g) * u)
    return linear(jnp.concatenate(cols, axis=1), p["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "quant",
                                             "tied"))
def _lm_head(params, x, b, rows, tokens, vocab, eps, quant, tied):
    """Final norm and LM head of sequence ``b`` of ``x`` (B, S, D) at its
    rows ``rows`` (R,), over the logical vocabulary. Returns, per row, how
    far the logit of ``tokens`` lies below the best one, the row's best
    token, and the best logit."""
    w = params["embed"]["table"][:vocab].T if tied \
        else params["head"]["w"][:, :vocab]
    xb = jax.lax.dynamic_index_in_dim(x, b, 0, keepdims=False)
    lg = linear(rmsnorm(xb[rows], params["ln_f"]["scale"], eps), w, quant)
    got = jnp.take_along_axis(lg, tokens[:, None], -1)[:, 0]
    top = jnp.max(lg, -1)
    return top - got, jnp.argmax(lg, -1).astype(jnp.int32), top


def _pad_seqs(seqs, block: int) -> np.ndarray:
    """Sequences as rows of one array, zero-padded at the end to a common
    multiple of ``block``."""
    S = max(len(q) for q in seqs)
    out = np.zeros((len(seqs), -(-S // block) * block), np.int32)
    for b, q in enumerate(seqs):
        out[b, :len(q)] = q
    return out


def head(params, m: dict, x, rows, tokens, quant) -> list:
    """For each sequence b of ``x`` (B, S, D): the gaps of ``tokens[b]``
    below the best logit at rows ``rows[b]``, the best tokens and the best
    logits, as numpy. Rows are padded to a power of two (one compile per bucket)."""
    out = []
    for b, (r, t) in enumerate(zip(rows, tokens)):
        n = len(r)
        width = max(16, 1 << (n - 1).bit_length())
        rp = np.full((width,), r[-1], np.int32)
        tp = np.zeros((width,), np.int32)
        rp[:n], tp[:n] = r, t
        gap, best, top = _lm_head(params, x, jnp.int32(b), jnp.asarray(rp),
                             jnp.asarray(tp), m["vocab_size"],
                             float(m["norm_eps"]), quant,
                             bool(m.get("tie_embeddings", False)))
        out.append((np.asarray(gap)[:n], np.asarray(best)[:n],
                    np.asarray(top)[:n]))
    return out


# ------------------------------------------------------------------- router
def t5_bucket(rel, n_buckets: int, max_distance: int):
    """T5's symmetric relative-position bucket of ``rel = key - query``:
    half the buckets per direction, exact below a quarter of them, then
    logarithmic up to ``max_distance``."""
    n = n_buckets // 2
    ret = jnp.where(rel > 0, n, 0)
    a = jnp.abs(rel)
    exact = n // 2
    large = exact + (jnp.log(a.astype(jnp.float32) / exact + 1e-6)
                     / np.log(max_distance / exact)
                     * (n - exact)).astype(jnp.int32)
    return ret + jnp.where(a < exact, a, jnp.minimum(large, n - 1))


@functools.partial(jax.jit, static_argnames=("heads", "buckets", "max_dist",
                                             "eps", "quant"))
def router_score(params, tokens, mask, heads, buckets, max_dist, eps,
                 quant=None):
    """The router's score of one padded prompt (S,) with validity ``mask``:
    a bidirectional pre-norm encoder with T5 relative-position biases,
    masked mean pooling and a two-layer tanh head, sigmoid at the end."""
    S = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(S)
    buckets = t5_bucket(pos[None, :] - pos[:, None], buckets, max_dist)
    table = params["rel_bias"].astype(jnp.float32)          # (buckets, H)
    # (H, S, S), shared by every layer: gathered once, not once a layer
    bias = jax.lax.map(lambda i: jnp.take(table[:, i], buckets),
                       jnp.arange(heads))
    keep = mask > 0

    def layer(x, p):
        D = x.shape[1]
        hd = D // heads
        h = rmsnorm(x, p["ln1"]["scale"], eps)
        qkv = linear(h, p["wqkv"].reshape(D, 3 * D), quant).reshape(
            S, 3, heads, hd)

        def one_head(i):    # (S, S) scores at a time, not (H, S, S)
            q, k, v = (jax.lax.dynamic_index_in_dim(qkv[:, j], i, 1, False)
                       for j in range(3))
            s = jnp.dot(q, k.T, precision=HI) * hd ** -0.5 \
                + jax.lax.dynamic_index_in_dim(bias, i, 0, False)
            s = jnp.where(keep[None, :], s, -jnp.inf)
            return jnp.dot(jax.nn.softmax(s, -1), v, precision=HI)

        o = jax.lax.map(one_head, jnp.arange(heads))           # (H, S, hd)
        o = jnp.transpose(o, (1, 0, 2)).reshape(S, D)
        x = x + linear(o, p["wo"], quant)
        h = rmsnorm(x, p["ln2"]["scale"], eps)
        return x + swiglu(h, p["mlp"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rmsnorm(x, params["ln_f"]["scale"], eps)
    w = keep.astype(jnp.float32)
    pooled = (x * w[:, None]).sum(0) / jnp.maximum(w.sum(), 1.0)
    hid = jnp.tanh(linear(pooled[None], params["head_w1"], quant))
    return jax.nn.sigmoid(linear(hid, params["head_w2"], quant)[0, 0])
