"""Work formulas: the operations and bytes the algorithm needs, from
shapes. Nothing here counts what a kernel walks or pads; a kernel's
roofline share is needed work over measured time.

Model dicts are a tier's ``model`` block of a configuration file. What
differs by architecture is in its kind file (``kinds/<kind>.py``); the
per-layer formulas here are shared by the kinds.
"""
from __future__ import annotations

from typing import Iterable, Optional, Tuple

from . import spec

BF16 = 2


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def decoder_layer_params(m: dict) -> int:
    """Matmul parameters of one dense decoder layer (norms left out)."""
    D, H, K, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      head_dim(m), m["d_ff"])
    return D * H * Dh + 2 * D * K * Dh + H * Dh * D + 3 * D * F


def mamba_layer_params(m: dict) -> int:
    D, N = m["d_model"], m["ssm_state"]
    di = m.get("ssm_expand", 2) * D
    H = di // m.get("ssm_headdim", 64)
    return D * (2 * di + 2 * N + H) + di * D


def lm_head_flops(m: dict) -> float:
    return 2.0 * m["d_model"] * m["vocab_size"]


def attention_flops(m: dict, keys: float) -> float:
    """QK^T and PV of one query over ``keys`` keys, all layers."""
    return 4.0 * keys * m["n_heads"] * head_dim(m) * m["n_layers"]


def token_flops(kind: str, m: dict, keys: float) -> float:
    """One token through the layers of a ``kind`` tier (not the LM head),
    attending over ``keys`` keys where it attends: the kind's own
    ``token_flops``. A kind with no file raises."""
    return spec.tier_kind(kind).token_flops(m, keys)


def prefill_flops(kind: str, m: dict, start: int, n: int) -> float:
    """A chunk of ``n`` prompt tokens after ``start`` resident ones."""
    mean_keys = start + (n + 1) / 2.0
    return n * token_flops(kind, m, mean_keys)


def router_flops(r: dict, L: int) -> float:
    """The router encoder over ``L`` real tokens and its scoring head."""
    D, F = r["d_model"], r["d_ff"]
    per_layer = 2.0 * L * (4 * D * D + 3 * D * F) + 4.0 * L * L * D
    return r["n_layers"] * per_layer + 2.0 * D * D + 2.0 * D


# ------------------------------------------------------------ paged kernels
def paged_decode_work(m: dict, contexts: Iterable[int],
                      layers: Optional[int] = None) -> Tuple[float, float]:
    """(flops, bytes) of the paged decode attention for one query per
    context (keys including the new token), over ``layers`` attention
    layers (all of the model's by default): the live K and V it must read,
    the query in and the output out."""
    H, K, Dh = m["n_heads"], m["n_kv_heads"], head_dim(m)
    L = m["n_layers"] if layers is None else layers
    flops = byts = 0.0
    for c in contexts:
        flops += 4.0 * c * H * Dh * L
        byts += (2.0 * c * K * Dh + 2.0 * H * Dh) * BF16 * L
    return flops, byts


def paged_prefill_work(m: dict, chunks: Iterable[Tuple[int, int]],
                       layers: Optional[int] = None) -> Tuple[float, float]:
    """(flops, bytes) of the paged prefill attention for chunks of
    (start, n) — n queries, causal over start + n keys — over ``layers``
    attention layers (all of the model's by default): K and V of the
    chunk's whole context read once, queries in, output out."""
    H, K, Dh = m["n_heads"], m["n_kv_heads"], head_dim(m)
    L = m["n_layers"] if layers is None else layers
    flops = byts = 0.0
    for s, n in chunks:
        flops += 4.0 * H * Dh * (n * s + n * (n + 1) / 2.0) * L
        byts += (2.0 * (s + n) * K * Dh + 2.0 * n * H * Dh) * BF16 * L
    return flops, byts


def roofline_share(flops: float, byts: float, seconds: float,
                   peaks: dict) -> float:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the measured time, in percent."""
    least = max(flops / peaks["bf16_flops_per_s"],
                byts / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
