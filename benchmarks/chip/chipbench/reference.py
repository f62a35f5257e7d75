"""Plain references of the served models and the router, in float32.

Each follows the architecture's published description, written again here
in straightforward ``jax.numpy`` with ``precision="highest"`` matmuls: no
kernel, cache, batching or chunking of the program's. It imports nothing
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
SEQ_BLOCK = 1024     # sequence bucket of the decoder references
Q_BLOCK = 256        # query rows per attention block
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


# ------------------------------------------------------------ dense decoder
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


class DecoderReference:
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


# ------------------------------------------------------------------ mamba-2
def _mamba_block(x, p, d_inner, state, headdim, eps, quant):
    """One Mamba-2 block, x + mixer(norm(x)), as the plain recurrence
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t."""
    s = p["ssm"]
    S = x.shape[0]
    di, N, P = d_inner, state, headdim
    H = di // P
    u = linear(rmsnorm(x, p["ln"]["scale"], eps), s["w_in"], quant)
    z, xbc, dt = u[:, :di], u[:, di:2 * di + 2 * N], u[:, 2 * di + 2 * N:]
    cw = s["conv_w"].astype(jnp.float32)                  # (W, channels)
    W = cw.shape[0]
    padded = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc], 0)
    conv = sum(padded[j:j + S] * cw[j] for j in range(W))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(S, H, P)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + s["dt_bias"].astype(jnp.float32))   # (S, H)
    A = -jnp.exp(s["A_log"].astype(jnp.float32))

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, Bm, Cm, dt), unroll=8)
    y = y + xs * s["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(S, di) * jax.nn.silu(z)
    y = rmsnorm(y, s["norm"]["scale"], eps)
    return x + linear(y, s["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("d_inner", "state", "headdim",
                                             "eps", "quant"))
def _mamba_stack(layers, x, d_inner, state, headdim, eps, quant):
    """Every block in turn over a batch of sequences ``x`` (B, S, D), one
    compile for the whole stack."""
    block = jax.vmap(_mamba_block, in_axes=(0, None, None, None, None, None,
                                            None))

    def body(x, p):
        return block(x, p, d_inner, state, headdim, eps, quant), None
    return jax.lax.scan(body, x, layers)[0]


class MambaReference:
    """Mamba-2 (SSD) language model: pre-norm Mamba-2 blocks, tied
    embedding and LM head."""

    def __init__(self, params, model: dict, quant: Optional[str] = None):
        self.p, self.m, self.quant = params, model, quant

    def gaps(self, seqs, rows, tokens) -> list:
        """As ``DecoderReference.gaps``; all sequences in one batch, so
        the recurrence's steps are shared."""
        m = self.m
        toks = jnp.asarray(_pad_seqs(seqs, SEQ_BLOCK))
        x = self.p["embed"]["table"][toks].astype(jnp.float32)
        x = _mamba_stack(self.p["layers"], x,
                         m.get("ssm_expand", 2) * m["d_model"],
                         m["ssm_state"], m.get("ssm_headdim", 64),
                         float(m["norm_eps"]), self.quant)
        return head(self.p, m, x, rows, tokens, self.quant)


REFERENCES = {"decoder": DecoderReference, "mamba2": MambaReference}


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
