"""Tier kind ``mamba2``: a Mamba-2 (SSD) language model.

The plain float32 reference of the architecture, the work of one token
through its layers, and how many of its layers keep K/V in the paged cache
(none; see ``chipbench.spec.tier_kind``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from chipbench import work
from chipbench.reference import (HI, SEQ_BLOCK, _pad_seqs, head, linear,
                                 rmsnorm)


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


class Reference:
    """Mamba-2 (SSD) language model: pre-norm Mamba-2 blocks, tied
    embedding and LM head."""

    def __init__(self, params, model: dict, quant: Optional[str] = None):
        self.p, self.m, self.quant = params, model, quant

    def gaps(self, seqs, rows, tokens) -> list:
        """As the ``decoder`` kind's ``Reference.gaps``; all sequences in
        one batch, so the recurrence's steps are shared."""
        m = self.m
        toks = jnp.asarray(_pad_seqs(seqs, SEQ_BLOCK))
        x = self.p["embed"]["table"][toks].astype(jnp.float32)
        x = _mamba_stack(self.p["layers"], x,
                         m.get("ssm_expand", 2) * m["d_model"],
                         m["ssm_state"], m.get("ssm_headdim", 64),
                         float(m["norm_eps"]), self.quant)
        return head(self.p, m, x, rows, tokens, self.quant)


def token_flops(m: dict, keys: float) -> float:
    """One token through the layers (not the LM head): matmuls, the state
    update and the convolution; ``keys`` does not enter."""
    di = m.get("ssm_expand", 2) * m["d_model"]
    P, N = m.get("ssm_headdim", 64), m["ssm_state"]
    state = 6.0 * (di // P) * P * N      # decay, update, read-out of h
    conv = 2.0 * m.get("ssm_conv_width", 4) * (di + 2 * N)
    return (2.0 * work.mamba_layer_params(m) + state + conv) * m["n_layers"]


def paged_layers(m: dict) -> int:
    """Attention-free: no layer keeps K/V."""
    return 0
