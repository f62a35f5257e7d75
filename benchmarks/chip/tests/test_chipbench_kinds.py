"""CPU tests of tier kinds (``kinds/<kind>.py``): a new architecture joins
the work formulas and the readers by a file of its own; an unknown kind
fails before any weight is made; the two kinds in the benchmark read what
they read before kinds were files; weights are scaled by the fan-in after
a leaf's stack axes."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import harness, readings, spec, weights, work

DECODE_OP = ("custom-call(s32[16,320]{1,0} %p, s32[16]{0} %l, s32[1]{0} %y, "
             "bf16[16,96,128]{2,1,0} %q), custom_call_target=tpu_custom_call")
PREFILL_OP = ("custom-call(s32[1,320]{1,0} %p, s32[1]{0} %s, s32[1]{0} %t, "
              "s32[1]{0} %y, bf16[1,256,96,128]{3,2,1,0} %q), "
              "custom_call_target=tpu_custom_call")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ------------------------------------------------- a kind joins by its file
TOY_KIND = '''
def token_flops(m, keys):
    return 1000.0 * m["n_layers"] + keys


def paged_layers(m):
    return 1


def stack_axes(names, shape):
    return 3 if "experts" in names else 0
'''
TOY = {"d_model": 8, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4,
       "n_layers": 4, "vocab_size": 16}


@pytest.fixture
def toy_kinds(tmp_path, monkeypatch):
    (tmp_path / "toy.py").write_text(TOY_KIND)
    monkeypatch.setattr(spec, "KINDS_DIR", tmp_path)
    return tmp_path


def test_a_kind_file_drives_the_work_and_the_readers(toy_kinds):
    kind = spec.tier_kind("toy")
    assert kind.paged_layers(TOY) == 1
    assert work.token_flops("toy", TOY, 5) == 4005.0
    rec = {"window": [0.0, 10.0],
           "requests": [{"submit": 1.0, "prompt_len": 3, "tier": 0,
                         "token_t": [2.0, 3.0]}],
           "router": {"d_model": 2, "d_ff": 4, "n_layers": 1},
           "tiers": [{"kind": "toy", "model": TOY}],
           "work": {"tier0.prefill": [(1.5, [(0, 3)])],
                    "tier0.decode": [(2.5, [4, 6])]},
           "spans": {"router": [(1.0, 1.5)], "tier0": [(1.5, 3.0)]},
           "peaks": PEAKS,
           "trace": {"op_s": {"dec": 2e-9, "pre": 1e-9},
                     "labels": {"dec": DECODE_OP, "pre": PREFILL_OP}}}
    flops = (work.router_flops(rec["router"], 3) + 3 * (4000.0 + 2.0)
             + 4004.0 + 4006.0 + 2 * work.lm_head_flops(TOY))
    assert readings.model_flops(rec) == pytest.approx(flops)
    # the paged kernels count the one layer the kind keeps in the cache
    one_layer = {**TOY, "n_layers": 1}
    assert spec.metric_reader("paged_decode_roofline")(rec) == \
        pytest.approx(work.roofline_share(
            *work.paged_decode_work(one_layer, [4, 6]), 2e-9, PEAKS))
    assert spec.metric_reader("paged_prefill_roofline")(rec) == \
        pytest.approx(work.roofline_share(
            *work.paged_prefill_work(one_layer, [(0, 3)]), 1e-9, PEAKS))


def test_a_kind_s_stack_axes_scale_an_expert_leaf_by_its_fan_in(toy_kinds):
    """A (periods, positions, experts, D, F) leaf whose kind counts three
    stack axes is scaled by 1/sqrt(D): the truncated normal on [-2, 2] has
    a standard deviation of 0.88 before scaling. The default rule would
    take the count of periods for the fan-in."""
    D = 256
    shapes = {"blocks": {"experts": {"w_in": jax.ShapeDtypeStruct(
        (1, 4, 8, D, 64), jnp.float32)}}}
    p = harness.make_tier_params({"reference": "toy"}, shapes,
                                 2 ** 31 + 5, 0)
    unit = math.sqrt(1 - 4 * math.exp(-2) / math.sqrt(2 * math.pi)
                     / math.erf(math.sqrt(2)))
    assert unit == pytest.approx(0.8796, abs=1e-4)
    got = float(np.std(np.asarray(p["blocks"]["experts"]["w_in"])))
    assert got == pytest.approx(unit / math.sqrt(D), rel=0.01)
    plain = weights.make_params(shapes, weights.seed_key(1, 0))
    assert float(np.std(np.asarray(plain["blocks"]["experts"]["w_in"]))) \
        == pytest.approx(unit, rel=0.01)


# ------------------------------------------------------ an unknown kind
def test_an_unknown_kind_fails_in_load_cell_and_in_the_work(tmp_path):
    bench = {"workloads": [{"name": "x.chat", "config": "x",
                            "traffic": "chat", "chips": 1}],
             "configs": [{"name": "x", "file": "x.json"}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    config = {**tiny.CONFIG, "tiers": [
        tiny.CONFIG["tiers"][0], {**tiny.CONFIG["tiers"][1],
                                  "reference": "no_such_kind"}]}
    (tmp_path / "x.json").write_text(json.dumps(config))
    with pytest.raises(FileNotFoundError, match="no_such_kind.py"):
        spec.load_cell("x.chat", root=tmp_path)
    with pytest.raises(FileNotFoundError, match="no_such_kind"):
        work.token_flops("no_such_kind", TOY, 10)
    with pytest.raises(FileNotFoundError, match="no_such_kind"):
        work.prefill_flops("no_such_kind", TOY, 0, 4)


# ------------------------------- the benchmark's kinds read as they did
def mlarge_record():
    """A fixed work record over ``mamba2-mlarge``'s two tiers: requests,
    prefill chunks and decode contexts of both, spans, and two kernels in
    the trace."""
    cfg = spec.load_config("mamba2-mlarge")
    return {
        "window": [100.0, 110.0],
        "requests": [
            {"submit": 99.5, "prompt_len": 700, "tier": 1,
             "token_t": [99.9, 100.2, 100.25]},
            {"submit": 100.5, "prompt_len": 356, "tier": 0,
             "token_t": [100.8, 100.81, 100.82, 110.5]},
            {"submit": 101.0, "prompt_len": 4096, "tier": 1,
             "token_t": [102.0, 102.05]},
            {"submit": 109.9, "prompt_len": 33, "tier": 0, "token_t": []}],
        "router": cfg["router"],
        "tiers": [{"name": t["name"], "kind": t["reference"],
                   "model": t["model"]} for t in cfg["tiers"]],
        "work": {"tier0.prefill": [(100.6, [(0, 256), (256, 100)]),
                                   (109.9, [(0, 33)])],
                 "tier0.decode": [(100.81, [357, 358]), (100.9, [359])],
                 "tier1.prefill": [(101.1, [(0, 256)]), (101.2, [(256, 256)]),
                                   (101.3, [(3840, 256)])],
                 "tier1.decode": [(100.2, [701, 1200, 4097]),
                                  (100.25, [702, 1201])]},
        "spans": {"router": [(100.5, 100.53), (101.0, 101.04)],
                  "tier0": [(100.6, 100.61), (100.81, 100.82)],
                  "tier1": [(100.2, 100.22), (101.1, 101.13),
                            (99.0, 99.5)]},
        "peaks": spec.load_peaks("TPU v5 lite"),
        "trace": {"op_s": {"dec": 0.0031, "pre": 0.0017, "fusion.1": 2.5},
                  "labels": {"dec": DECODE_OP, "pre": PREFILL_OP}},
    }


def test_mlarge_work_and_readings_are_the_parent_s():
    """Golden values, computed with the harness as it was before tier
    kinds were files (``REFERENCES`` and the Mamba-2 fall-through)."""
    rec = mlarge_record()
    m0, m1 = (t["model"] for t in rec["tiers"])
    assert readings.model_flops(rec) == 9752216758272.0
    assert readings.mfu(rec) == 35.35974169061625
    assert spec.metric_reader("paged_decode_roofline")(rec) == \
        2.568691638110993
    assert spec.metric_reader("paged_prefill_roofline")(rec) == \
        33.67591527500747
    ctx = [c for _, cs in rec["work"]["tier1.decode"] for c in cs]
    chunks = [c for _, cs in rec["work"]["tier1.prefill"] for c in cs]
    assert work.paged_decode_work(m1, ctx) == (776699904.0, 65216512.0)
    assert work.paged_prefill_work(m1, chunks) == \
        (112780640256.0, 115343360.0)
    assert work.token_flops("mamba2", m0, 500) == 208846848.0
    assert spec.tier_kind("mamba2").paged_layers(m0) == 0
    assert spec.tier_kind("decoder").paged_layers(m1) == m1["n_layers"]


# ---------------------------------------------------------------- weights
def old_leaf(key, names, shape, dtype, embed_std):
    """The weight rule as it was before stack axes were counted: fan-in
    from axis 1 under ``layers``, from axis 0 elsewhere."""
    name = names[-1]
    stacked = "layers" in names
    tn = lambda: jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)
    if name == "table" or names == ["embed"]:
        return tn() * jnp.asarray(embed_std, dtype)
    if name in ("scale", "D"):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if name == "rel_bias":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        dt = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
        return jnp.log(jnp.expm1(dt)).astype(dtype)
    if name == "conv_w":
        return tn() * jnp.asarray(0.2, dtype)
    if len(shape) >= 2 + stacked:
        fan_in = shape[1] if stacked else shape[0]
        return tn() * jnp.asarray(fan_in ** -0.5, dtype)
    raise ValueError(names)


def old_fill(shapes, key, embed_std=0.02):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        return jax.tree_util.tree_unflatten(treedef, [
            old_leaf(jax.random.fold_in(key, i), weights._names(path),
                     tuple(s.shape), s.dtype, embed_std)
            for i, (path, s) in enumerate(flat)])
    return jax.jit(fill)(key)


def test_default_stack_axes_fill_the_tiny_cell_as_before():
    """Every leaf of the tiny cell's two tiers (through their kinds) and of
    its router is bit for bit what the old rule made."""
    from repro.models import build_model
    from repro.models.encoder import init_router_encoder
    seed = 2 ** 31 + 99
    for t, tier in enumerate(tiny.CONFIG["tiers"]):
        bundle = build_model(harness.arch_config(tier["model"]))
        shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
        new = harness.make_tier_params(tier, shapes, seed, t)
        old = old_fill(shapes, weights.seed_key(seed, t))
        assert_same(new, old)
    rcfg = harness.router_config(tiny.CONFIG["router"])
    rshapes = jax.eval_shape(lambda k: init_router_encoder(k, rcfg),
                             jax.random.PRNGKey(0))
    key = weights.seed_key(1, 2)
    assert_same(weights.make_params(rshapes, key), old_fill(rshapes, key))


def assert_same(new, old):
    a, ta = jax.tree_util.tree_flatten_with_path(new)
    b, tb = jax.tree_util.tree_flatten_with_path(old)
    assert ta == tb
    for (path, x), (_, y) in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape, path
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), path
