"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e, at the widths of the published tiers the chip smoke
serves (gemma3-4b attention, mamba2-130m SSD). Interpret mode never checks
TPU block tiling; the TPU compiler here does, with no chip attached. Each
test asserts the kernel survived as a ``tpu_custom_call``, and the kernels
a device trace reads by name carry their ``pallas_call`` name.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU compiler library, and every
xdist worker imports this module.
"""
import ast
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_gqa
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.paged_decode_attention.kernel import \
    paged_decode_attention_gqa
from repro.kernels.paged_prefill_attention.kernel import \
    paged_prefill_attention_gqa
from repro.kernels.ssd_scan.kernel import ssd_chunk_scan

# gemma3-4b serving geometry: 34 attention layers, 8 slots, 4 KV heads x 2
# query heads each, head_dim 256, 16-token pages, 2048-token slots -> 128
# pages per slot. The kernels read one layer (a traced scalar, as under the
# serving path's layer scan) of the whole stacked pool.
L, B, K, G, D, PS, MP = 34, 8, 4, 2, 256, 16, 128
POOL = (L, 1 + B * MP, K, PS, D)
WINDOW = 1024
# Mistral-Large-2407 tier-1 serving geometry (the chip benchmark's tier 1):
# a 2-layer stage, 16 slots of 5,120 tokens, 8 KV heads x 12 query heads,
# head_dim 128, 16-token pages
ML_B, ML_K, ML_G, ML_D, ML_MP = 16, 8, 12, 128, 320
ML_POOL = (2, 1 + ML_B * ML_MP, ML_K, PS, ML_D)
ROOFLINE_METRIC = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
                   / "metrics" / "paged_decode_roofline.py")
# mamba2-130m SSD geometry: 24 heads of 64 channels, state 128
SSD_H, SSD_P, SSD_N = 24, 64, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args, name=None):
    """``name``: the kernel's ``pallas_call`` name, which its custom call
    must carry in the compiled text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if name is not None:
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(", text), \
            f"no custom call named {name!r}"


@pytest.mark.parametrize("pages_bound,pages_start,window", [
    (None, 0, 0), (2, 0, 0), (MP, MP // 2, WINDOW)])
def test_paged_decode_compiles_gemma3(one_chip, pages_bound, pages_start,
                                      window):
    def fn(q, kp, vp, layer, pt, sl):
        return paged_decode_attention_gqa(
            q, kp, vp, layer, pt, sl, pages_bound=pages_bound,
            pages_start=pages_start, window=window, interpret=False)
    _assert_kernel_compiles(
        fn, _spec(one_chip, (B, K, G, D)),
        _spec(one_chip, POOL), _spec(one_chip, POOL),
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (B, MP), jnp.int32), _spec(one_chip, (B,), jnp.int32),
        name="paged_decode_attention")


def _roofline_pattern() -> str:
    """The ``KERNEL`` pattern by which the benchmark's decode roofline
    finds the kernel in a device trace, read from its file."""
    tree = ast.parse(ROOFLINE_METRIC.read_text())
    for node in tree.body:
        if isinstance(node, ast.Assign) and \
                [t.id for t in node.targets] == ["KERNEL"]:
            return ast.literal_eval(node.value)
    raise AssertionError(f"no KERNEL in {ROOFLINE_METRIC}")


@pytest.mark.parametrize("pages_bound", [128, 320])
def test_paged_decode_compiles_mlarge_and_stays_findable(one_chip,
                                                         pages_bound):
    """The decode kernel at tier 1's geometry compiles, and its custom call,
    printed with operand shapes as the device trace shows it, still matches
    the roofline metric's pattern: page table, lengths and layer, then the
    bf16 query."""
    from jax._src.lib import xla_client

    def fn(q, kp, vp, layer, pt, sl):
        return paged_decode_attention_gqa(q, kp, vp, layer, pt, sl,
                                          pages_bound=pages_bound,
                                          interpret=False)
    args = (_spec(one_chip, (ML_B, ML_K, ML_G, ML_D)),
            _spec(one_chip, ML_POOL), _spec(one_chip, ML_POOL),
            _spec(one_chip, (), jnp.int32),
            _spec(one_chip, (ML_B, ML_MP), jnp.int32),
            _spec(one_chip, (ML_B,), jnp.int32))
    _assert_kernel_compiles(fn, *args, name="paged_decode_attention")
    compiled = jax.jit(fn).lower(*args).compile()
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    text = "\n".join(m.to_string(opts)
                     for m in compiled.runtime_executable().hlo_modules())
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    assert re.search(_roofline_pattern(), calls[0]), calls[0][:300]


@pytest.mark.parametrize("C,pages_bound,pages_start,window", [
    (16, 1, 0, 0), (8, None, 0, 0), (16, MP, MP // 2, WINDOW)])
def test_paged_prefill_compiles_gemma3(one_chip, C, pages_bound, pages_start,
                                       window):
    def fn(q, kp, vp, layer, pt, start, total):
        return paged_prefill_attention_gqa(
            q, kp, vp, layer, pt, start, total, pages_bound=pages_bound,
            pages_start=pages_start, window=window, interpret=False)
    _assert_kernel_compiles(
        fn, _spec(one_chip, (B, K, C, G, D)),
        _spec(one_chip, POOL), _spec(one_chip, POOL),
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (B, MP), jnp.int32), _spec(one_chip, (B,), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), name="paged_prefill_attention")


@pytest.mark.parametrize("chunk", [16, 256])  # serving chunk, model chunk
def test_ssd_chunk_scan_compiles_mamba2(one_chip, chunk):
    bc = 2
    _assert_kernel_compiles(
        lambda *a: ssd_chunk_scan(*a, interpret=False),
        _spec(one_chip, (bc, SSD_H, chunk, SSD_P), jnp.float32),
        _spec(one_chip, (bc, SSD_H, chunk, 1), jnp.float32),
        _spec(one_chip, (bc, SSD_H, chunk, 1), jnp.float32),
        _spec(one_chip, (bc, chunk, SSD_N), jnp.float32),
        _spec(one_chip, (bc, chunk, SSD_N), jnp.float32), name="ssd_scan")


def test_flash_attention_compiles(one_chip):
    S = 1024
    _assert_kernel_compiles(
        lambda q, k, v: flash_attention_bhsd(q, k, v, causal=True,
                                             window=WINDOW, interpret=False),
        *[_spec(one_chip, (B * K * G, S, D)) for _ in range(3)])


@pytest.mark.parametrize("S", [2048, 200])  # block-multiple, padded
def test_dense_decode_compiles_gemma3(one_chip, S):
    _assert_kernel_compiles(
        lambda q, k, v, valid: decode_attention_gqa(
            q, k, v, valid, bk=min(512, S), interpret=False),
        _spec(one_chip, (B * K, G, D)), _spec(one_chip, (B * K, S, D)),
        _spec(one_chip, (B * K, S, D)), _spec(one_chip, (B * K, S), jnp.int8))
