"""Sharding rules: valid, divisibility-aware specs for every assigned arch,
and an end-to-end mini dry-run on 8 placeholder devices (subprocess)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.inputs import dryrun_config, params_specs
from repro.models.config import INPUT_SHAPES


def _fake_mesh_shape(shape_dict):
    class FakeMesh:
        shape = shape_dict
    return FakeMesh()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divisible(arch):
    """Every sharded dim must divide its mesh axis (16/16)."""
    from repro.sharding.rules import param_spec
    cfg = dryrun_config(get_config(arch), INPUT_SHAPES["train_4k"])
    shapes = params_specs(cfg)
    mesh = _fake_mesh_shape({"data": 16, "model": 16})
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    sizes = {"data": 16, "model": 16}
    n_sharded = 0
    for path, leaf in leaves:
        spec = param_spec(path, leaf.shape, mesh)
        assert len(spec) <= len(leaf.shape), (path, spec)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 9):
            if ax is not None:
                assert dim % sizes[ax] == 0, (jax.tree_util.keystr(path),
                                              leaf.shape, spec)
                n_sharded += 1
    assert n_sharded > 0, "no parameter got sharded at all"


@pytest.mark.parametrize("arch", ["grok-1-314b", "mistral-large-123b"])
def test_big_arch_fits_param_budget(arch):
    """2D-sharded bf16 params must be << HBM per chip."""
    from repro.sharding.rules import param_spec
    cfg = dryrun_config(get_config(arch), INPUT_SHAPES["prefill_32k"])
    shapes = params_specs(cfg)
    mesh = _fake_mesh_shape({"data": 16, "model": 16})
    sizes = {"data": 16, "model": 16}
    per_dev = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        spec = param_spec(path, leaf.shape, mesh)
        shard = 1
        for ax in spec:
            if ax is not None:
                shard *= sizes[ax]
        per_dev += np.prod(leaf.shape) * leaf.dtype.itemsize / shard
    assert per_dev / 1e9 < 4.0, f"{per_dev/1e9:.1f} GB/device"


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.models.config import ArchConfig, InputShape
from repro.models.model import build_model
from repro.launch.steps import build_train_step, DRYRUN_OPT
from repro.launch.inputs import input_specs
from repro.sharding import rules
from repro.sharding.context import activation_sharding
from repro.training.optim import init_opt_state

cfg = ArchConfig("mini", "moe", 4, 64, 4, 2, 128, 512, head_dim=16,
                 n_experts=4, top_k=2, dtype="bfloat16", vocab_pad_multiple=64,
                 attn_chunk=64)
shape = InputShape("mini", 128, 16, "train")
mesh = jax.make_mesh((4, 2), ("data", "model"), devices=jax.devices(),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
bundle = build_model(cfg)
p_specs = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
b_specs = input_specs(cfg, shape)
opt_specs = jax.eval_shape(lambda p: init_opt_state(p, DRYRUN_OPT), p_specs)
fn = build_train_step(bundle)
in_sh = (rules.params_shardings(p_specs, mesh),
         {"m": rules.params_shardings(opt_specs["m"], mesh),
          "v": rules.params_shardings(opt_specs["v"], mesh),
          "step": rules.replicated(opt_specs["step"], mesh)},
         rules.batch_shardings(b_specs, mesh, 16))
ba = rules.batch_axes(mesh, 16)
with mesh, activation_sharding(mesh, ba):
    compiled = jax.jit(fn, in_shardings=in_sh).lower(
        p_specs, opt_specs, b_specs).compile()
ma = compiled.memory_analysis()
print(json.dumps({"ok": True, "temp_gb": ma.temp_size_in_bytes / 1e9}))
"""


def test_mini_dryrun_8dev_subprocess():
    """Full lower+compile of a sharded train step on 8 placeholder devices."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]


FLASH_DECODE_CHECK = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models.attention import decode_attention, init_attention
from repro.models.config import ArchConfig
from repro.sharding.context import flash_decode
mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices(),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = ArchConfig("t", "dense", 2, 64, 4, 2, 0, 256, head_dim=16, attn_chunk=8)
p = init_attention(jax.random.PRNGKey(0), cfg)
B, S = 4, 32
k = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, 16))
v = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2, 16))
x = jax.random.normal(jax.random.PRNGKey(3), (B, 1, 64))
for pos_val in (0, 7, 17, 31):
    pos = jnp.array(pos_val, jnp.int32)
    ref_out, rk, rv = decode_attention(p, x, k, v, pos, cfg)
    with mesh, flash_decode(mesh, "data"):
        f_out, fk, fv = jax.jit(lambda *a: decode_attention(p, *a, cfg))(
            x, k, v, pos)
    np.testing.assert_allclose(np.asarray(ref_out), np.asarray(f_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(fk), rtol=1e-6,
                               atol=1e-6)
print("FLASH_OK")
"""


def test_flash_decode_matches_reference_subprocess():
    """shard_map flash-decode == single-device reference, incl. the
    shard-local cache update, across positions (every shard owns pos once)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", FLASH_DECODE_CHECK], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FLASH_OK" in out.stdout
