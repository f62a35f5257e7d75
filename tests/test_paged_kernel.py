"""Paged decode-attention kernel vs the pure-jnp paged reference and the
dense decode oracle, across GQA group sizes, page sizes, and ragged
seq_lens (interpret mode on CPU)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import ref as da_ref
from repro.kernels.paged_decode_attention import ops as pda_ops
from repro.kernels.paged_decode_attention.kernel import \
    paged_decode_attention_gqa
from repro.kernels.paged_decode_attention.ref import paged_decode_attention_ref


LAYER = 1   # the pool layer the kernels read; the references get kp[LAYER]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)


def _make_paged(rng, B, K, D, ps, MP, lens):
    """Random stacked page pools (LAYER + 1, P, K, ps, D) + a page table
    giving each request distinct pages."""
    n_pages = 1 + sum(-(-int(l) // ps) for l in lens)  # page 0 reserved
    # LAYER + 1 layers: the kernels must read LAYER, not another layer
    shape = (LAYER + 1, n_pages, K, ps, D)
    kp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pt = np.zeros((B, MP), np.int32)
    nxt = 1
    for b in range(B):
        for i in range(-(-int(lens[b]) // ps)):
            pt[b, i] = nxt
            nxt += 1
    return kp, vp, jnp.asarray(pt)


@pytest.mark.parametrize("G,ps,D", [(1, 8, 32), (2, 16, 64), (4, 8, 128),
                                    (8, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_ref(G, ps, D, dtype):
    rng = np.random.default_rng(G * ps + D)
    B, K, MP = 3, 2, 6
    lens = jnp.asarray(rng.integers(1, MP * ps + 1, (B,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, K, G, D)), dtype) * (D ** -0.5)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, np.asarray(lens))
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    out = paged_decode_attention_gqa(q, kp, vp, LAYER, pt, lens,
                                     interpret=True)
    ref = paged_decode_attention_ref(q, kp[LAYER], vp[LAYER], pt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_paged_matches_dense_decode_oracle():
    """Gathering pages into a dense cache and running the dense decode
    reference must agree with the paged path — layout equivalence."""
    rng = np.random.default_rng(11)
    B, K, G, D, ps, MP = 2, 2, 4, 32, 8, 4
    lens = jnp.asarray([5, 29], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, K, G, D)), jnp.float32) * (D ** -0.5)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, np.asarray(lens))
    out = paged_decode_attention_gqa(q, kp, vp, LAYER, pt, lens,
                                     interpret=True)

    # densify: (B, MP, K, ps, D) -> (B*K, S, D) with per-row validity
    S = MP * ps
    kd = jnp.moveaxis(kp[LAYER][pt], 2, 1).reshape(B * K, S, D)
    vd = jnp.moveaxis(vp[LAYER][pt], 2, 1).reshape(B * K, S, D)
    valid = (jnp.arange(S)[None] < lens[:, None]).astype(jnp.int8)
    valid = jnp.repeat(valid, K, axis=0)
    ref = da_ref.decode_attention_ref(q.reshape(B * K, G, D), kd, vd, valid)
    np.testing.assert_allclose(np.asarray(out).reshape(B * K, G, D),
                               np.asarray(ref), rtol=3e-5, atol=3e-5)


def test_paged_ops_layout():
    """Model entry: q (B, H, D) regrouped to GQA, H = K * G."""
    rng = np.random.default_rng(4)
    B, K, G, D, ps, MP = 2, 2, 2, 32, 8, 3
    H = K * G
    lens = jnp.asarray([7, 20], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32) * (D ** -0.5)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, np.asarray(lens))
    out = pda_ops.paged_decode_attention(q, kp, vp, LAYER, pt, lens)
    ref = paged_decode_attention_ref(q.reshape(B, K, G, D), kp[LAYER],
                                     vp[LAYER], pt, lens)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref).reshape(B, H, D),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("bound", [1, 2, 3, 6])
def test_paged_kernel_live_bound_matches_full_walk(bound):
    """A pages_bound covering every seq_len must reproduce the full static
    page walk exactly (kernel and ref) across ragged lengths — the
    live-bounded dispatch is a pure compute saving, not a semantics
    change."""
    rng = np.random.default_rng(21 + bound)
    B, K, G, D, ps, MP = 3, 2, 2, 32, 8, 6
    lens = jnp.asarray(rng.integers(1, bound * ps + 1, (B,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, K, G, D)), jnp.float32) \
        * (D ** -0.5)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, np.asarray(lens))
    full = paged_decode_attention_gqa(q, kp, vp, LAYER, pt, lens,
                                      interpret=True)
    bk = paged_decode_attention_gqa(q, kp, vp, LAYER, pt, lens,
                                    pages_bound=bound, interpret=True)
    br = paged_decode_attention_ref(q, kp[LAYER], vp[LAYER], pt, lens,
                                    pages_bound=bound)
    np.testing.assert_allclose(np.asarray(bk), np.asarray(full),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(br), np.asarray(full),
                               rtol=3e-5, atol=3e-5)
    # the ops wrapper threads the bound through too
    H = K * G
    ob = pda_ops.paged_decode_attention(q.reshape(B, H, D), kp, vp, LAYER,
                                        pt, lens, pages_bound=bound)
    np.testing.assert_allclose(np.asarray(ob),
                               np.asarray(full).reshape(B, H, D),
                               rtol=3e-5, atol=3e-5)


def test_paged_masks_scratch_page_reads():
    """Entries past a request's length point at page 0 (scratch); whatever
    lives there must never leak into the output."""
    rng = np.random.default_rng(9)
    B, K, G, D, ps, MP = 1, 1, 2, 32, 8, 4
    lens = jnp.asarray([3], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, K, G, D)), jnp.float32)
    kp, vp, pt = _make_paged(rng, B, K, D, ps, MP, np.asarray(lens))
    out1 = paged_decode_attention_gqa(q, kp, vp, LAYER, pt, lens,
                                      interpret=True)
    # poison the scratch page with huge values
    kp2 = kp.at[LAYER, 0].set(100.0)
    vp2 = vp.at[LAYER, 0].set(-100.0)
    out2 = paged_decode_attention_gqa(q, kp2, vp2, LAYER, pt, lens,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)
