"""Mamba-2 SSD intra-chunk Pallas TPU kernel.

Computes, per (batch, chunk, ssd-head):
  y_diag[i]  = sum_{j<=i} (C_i . B_j) * exp(dAcum_i - dAcum_j) * dt_j * x_j
  state      = sum_j exp(dAcum_last - dAcum_j) * dt_j * B_j (x) x_j

i.e. the quadratic "attention-like" half of state-space duality plus the
chunk's contribution to the inter-chunk recurrence. The inter-chunk scan
stays in XLA (lax.scan) — it is O(n_chunks) and latency-bound, not
compute-bound; the matmuls here are what the MXU should run.

Grid = (batch*chunks, heads). Per program the working set is
(l,P) x + (l,N) B,C + (l,l) decay — for l=256, P=64, N=128 ≈ 0.5 MB fp32,
comfortably inside VMEM; l and N are 128-multiples for MXU alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, dacum_ref, b_ref, c_ref, y_ref, st_ref, *, l: int):
    x = x_ref[0].astype(jnp.float32)        # (l, P)
    dt = dt_ref[0].astype(jnp.float32)      # (l, 1)
    da = dacum_ref[0].astype(jnp.float32)   # (l, 1)
    B = b_ref[0].astype(jnp.float32)        # (l, N)
    C = c_ref[0].astype(jnp.float32)        # (l, N)

    # decay(i, j) = exp(da_i - da_j) for j <= i else 0
    rel = da - da.T                          # (l, l) via broadcast of (l,1)
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    decay = jnp.where(jj <= ii, jnp.exp(rel), 0.0)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (l, l)
    gated = scores * decay * dt.T            # dt_j on the j axis
    y = jax.lax.dot_general(gated, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (l, P)
    y_ref[0] = y.astype(y_ref.dtype)

    # state = sum_j w_j * B_j ⊗ x_j,  w_j = exp(da_last - da_j) * dt_j
    w = jnp.exp(da[l - 1] - da) * dt         # (l, 1)
    bw = B * w                               # (l, N)
    st = jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)      # (N, P)
    st_ref[0] = st.astype(st_ref.dtype)


def ssd_chunk_scan(x, dt, dacum, B, C, *, interpret: bool | None = None):
    """x: (BC, H, l, P); dt, dacum: (BC, H, l, 1); B, C: (BC, l, N) shared
    across heads (pre-broadcast by ops). Returns (y (BC,H,l,P) fp32,
    states (BC,H,N,P) fp32). BC = batch*chunks. ``interpret=None``
    auto-detects the backend."""
    from repro.kernels.common import default_interpret
    interpret = default_interpret(interpret)
    bc, H, l, P = x.shape
    N = B.shape[-1]
    xf = x.reshape(bc * H, l, P)
    dtf = dt.reshape(bc * H, l, 1)
    daf = dacum.reshape(bc * H, l, 1)
    Bf = jnp.broadcast_to(B[:, None], (bc, H, l, N)).reshape(bc * H, l, N)
    Cf = jnp.broadcast_to(C[:, None], (bc, H, l, N)).reshape(bc * H, l, N)
    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, l=l),
        grid=(bc * H,),
        in_specs=[
            pl.BlockSpec((1, l, P), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, N), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, l, P), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, N, P), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bc * H, l, P), jnp.float32),
            jax.ShapeDtypeStruct((bc * H, N, P), jnp.float32),
        ],
        interpret=interpret,
        name="ssd_scan",
    )(xf, dtf, daf, Bf, Cf)
    return y.reshape(bc, H, l, P), st.reshape(bc, H, N, P)
