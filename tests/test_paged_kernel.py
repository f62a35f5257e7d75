"""Paged decode-attention kernel vs the pure-jnp paged reference and the
dense decode oracle, across GQA group sizes, page sizes, and ragged
seq_lens (interpret mode on CPU)."""
import numpy as np
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import ref as da_ref
from repro.kernels.paged_decode_attention import ops as pda_ops
from repro.kernels.paged_decode_attention.kernel import \
    block_pages, paged_decode_attention_gqa, walk_blocks
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


# --------------------------------------------------- block walk and skip
# The TPU interpreter: uninitialised VMEM reads as NaN, and a read out of
# bounds (of the page table or of a pool) raises.
TPU_INTERPRET = pltpu.InterpretParams()


def _check(q, kp, vp, pt, lens, dtype=jnp.float32, **walk):
    out = paged_decode_attention_gqa(q, kp, vp, LAYER, jnp.asarray(pt),
                                     jnp.asarray(lens, jnp.int32),
                                     interpret=TPU_INTERPRET, **walk)
    ref = paged_decode_attention_ref(q, kp[LAYER], vp[LAYER],
                                     jnp.asarray(pt),
                                     jnp.asarray(lens, jnp.int32), **walk)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    return out


@pytest.mark.parametrize("pages_bound", [None, 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_block_edges(pages_bound, dtype):
    """Lengths of 0, 1, ps-1, ps, block-1, block and block+1 tokens, over
    walks of 20 and 17 pages, neither a multiple of a block's 16: the last
    block of the walk is partial, the last live block of a slot copies only
    its live pages, and stale (NaN) buffer rows never reach the output."""
    ps, K, G, D, MP = 16, 2, 2, 32, 20
    walk = MP if pages_bound is None else pages_bound
    ppb = block_pages(ps, K, D, jnp.dtype(dtype).itemsize, walk)
    assert ppb == 16 and walk % ppb
    blk = ppb * ps
    lens = [0, 1, ps - 1, ps, blk - 1, blk, blk + 1]
    rng = np.random.default_rng(31)
    kp, vp, pt = _make_paged(rng, len(lens), K, D, ps, MP, lens)
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    q = jnp.asarray(rng.standard_normal((len(lens), K, G, D)), dtype) \
        * (D ** -0.5)
    out = _check(q, kp, vp, pt, lens, dtype, pages_bound=pages_bound)
    assert not np.asarray(out[0]).any()      # a slot of length 0: zeros


def test_paged_kernel_reads_nothing_past_the_walk():
    """A slot whose length runs past ``pages_bound`` (one the engine's step
    masks) is clamped to the walk: handed a page table exactly the walk
    wide, the kernel reads no entry past it (the interpreter would raise)
    and attends to that slot's first ``pages_bound`` pages, as the
    reference does."""
    ps, K, G, D, bound = 16, 2, 2, 32, 20
    lens = [40, 400, 300, 321]
    rng = np.random.default_rng(5)
    kp, vp, pt = _make_paged(rng, len(lens), K, D, ps, 25, lens)
    q = jnp.asarray(rng.standard_normal((len(lens), K, G, D)), jnp.float32)
    _check(q, kp, vp, pt[:, :bound], lens, pages_bound=bound)


@pytest.mark.parametrize("window,lens,pages_start,pages_bound", [
    # windows start inside blocks of a 56-page walk from page 8
    (300, [520, 700, 777, 1000, 0], 8, 64),
    # a walk shorter than a block
    (40, [300, 290, 301], 8, 19),
])
def test_paged_kernel_window_walk(window, lens, pages_start, pages_bound):
    """Window layers walk from ``pages_start > 0`` and skip, per slot, the
    blocks wholly before the slot's window; a window that starts inside a
    block masks that block's older keys."""
    ps, K, G, D = 16, 2, 2, 32
    rng = np.random.default_rng(window)
    kp, vp, pt = _make_paged(rng, len(lens), K, D, ps, pages_bound, lens)
    q = jnp.asarray(rng.standard_normal((len(lens), K, G, D)), jnp.float32)
    _check(q, kp, vp, pt, lens, pages_bound=pages_bound,
           pages_start=pages_start, window=window)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_ignores_poisoned_unowned_pages(dtype):
    """Every page no slot owns, the scratch page 0 among them, holds NaN in
    K and inf in V, and so do the rows past each slot's length in its last
    page; table entries past a slot's length point at such pages. The
    kernel must match the reference run on the same pools with the poison
    zeroed: masked keys are selected away, never multiplied by zero."""
    ps, K, G, D, MP = 16, 2, 2, 32, 24
    lens = [1, 37, 256, 300, 0, 383]
    rng = np.random.default_rng(17)
    n_own = sum(-(-n // ps) for n in lens)
    n_pages = 2 * n_own + 1
    own = 1 + rng.permutation(n_pages - 1)[:n_own]       # scattered pages
    free = np.setdiff1d(np.arange(n_pages), own)          # 0 included
    pt = rng.choice(free, (len(lens), MP)).astype(np.int32)
    live = np.zeros((n_pages, ps), bool)                  # real keys
    nxt = 0
    for b, n in enumerate(lens):
        for i in range(-(-n // ps)):
            pt[b, i] = own[nxt]
            live[own[nxt], :min(ps, n - i * ps)] = True
            nxt += 1
    shape = (LAYER + 1, n_pages, K, ps, D)
    kp = jnp.asarray(rng.standard_normal(shape), dtype)
    vp = jnp.asarray(rng.standard_normal(shape), dtype)
    mask = jnp.asarray(live)[None, :, None, :, None]
    q = jnp.asarray(rng.standard_normal((len(lens), K, G, D)), dtype) \
        * (D ** -0.5)
    out = paged_decode_attention_gqa(
        q, jnp.where(mask, kp, jnp.nan), jnp.where(mask, vp, jnp.inf),
        LAYER, jnp.asarray(pt), jnp.asarray(lens, jnp.int32),
        interpret=TPU_INTERPRET)
    zero = jnp.zeros((), dtype)
    ref = paged_decode_attention_ref(
        q, jnp.where(mask, kp, zero)[LAYER], jnp.where(mask, vp, zero)[LAYER],
        jnp.asarray(pt), jnp.asarray(lens, jnp.int32))
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,pages_start", [(0, 0), (300, 8)])
def test_walk_blocks_counts_live_blocks(window, pages_start):
    """``walk_blocks`` (the engine's counter) against a block-by-block
    count of the blocks holding a key the query may attend, and the walked
    count against the kernel's launch grid."""
    from repro.analysis import pallas_check
    ps, K, G, D, bound = 16, 2, 2, 32, 64
    lens = [0, 1, 255, 256, 257, 520, 1000, 1100]
    walked, live = walk_blocks(lens, page_size=ps, n_kv_heads=K, head_dim=D,
                               itemsize=4, pages_bound=bound,
                               pages_start=pages_start, window=window)
    ppb = block_pages(ps, K, D, 4, bound - pages_start)
    n_blocks = -(-(bound - pages_start) // ppb)
    expect = 0
    for n in lens:
        for j in range(n_blocks):
            first = pages_start + j * ppb
            expect += any(
                p * ps < n and (not window or (p + 1) * ps > n - window)
                for p in range(first, min(first + ppb, bound)))
    assert (walked, live) == (len(lens) * n_blocks, expect)
    assert 0 < live < walked
    with pallas_check.capture() as rec:
        paged_decode_attention_gqa(
            np.zeros((len(lens), K, G, D), np.float32),
            np.zeros((LAYER + 1, 4, K, ps, D), np.float32),
            np.zeros((LAYER + 1, 4, K, ps, D), np.float32), LAYER,
            np.zeros((len(lens), bound), np.int32), np.asarray(lens, np.int32),
            pages_bound=bound, pages_start=pages_start, window=window)
    (call,) = rec.calls
    assert call.grid == (len(lens), n_blocks)
