"""A tiny cell of the benchmark's shape, for CPU tests: a two-layer Mamba-2
tier and a two-layer GQA decoder tier behind a two-layer router, at widths
the Pallas interpreter runs in seconds."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
for p in (CHIP, CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

ENGINE = {"n_slots": 4, "max_seq": 64, "max_new_tokens": 16,
          "prefill_chunk": 16, "prefill_budget": 16, "prefill_pack": 1}

CONFIG = {
    "name": "tiny",
    "tiers": [
        {"name": "m", "reference": "mamba2",
         "model": {"name": "m", "family": "ssm", "n_layers": 2,
                   "d_model": 64, "n_heads": 0, "n_kv_heads": 0, "d_ff": 0,
                   "vocab_size": 512, "ssm_state": 16, "ssm_headdim": 16,
                   "ssm_expand": 2, "ssm_chunk": 8, "ssm_conv_width": 4,
                   "tie_embeddings": True, "norm_eps": 1e-5,
                   "vocab_pad_multiple": 64},
         "engine": {**ENGINE, "walk_bound": "static"}},
        {"name": "d", "reference": "decoder",
         "model": {"name": "d", "family": "dense", "n_layers": 2,
                   "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                   "head_dim": 16, "d_ff": 128, "vocab_size": 512,
                   "rope_theta": 1e6, "norm_eps": 1e-5,
                   "tie_embeddings": False, "vocab_pad_multiple": 64},
         "engine": {**ENGINE, "walk_bound": "live"}},
    ],
    "router": {"vocab_size": 512, "n_layers": 2, "d_model": 32, "n_heads": 2,
               "d_ff": 64, "max_seq": 64, "rel_buckets": 8,
               "rel_max_distance": 16, "norm_eps": 1e-6, "tier0_share": 0.4,
               "calibration_prompts": 8, "weights_seed": 1},
    "knee_rps": 5.0,
    # set from the tiny cell's own readings on the CPU over nine seeds:
    # the program read at most 0.0036 / 0.0127 / 0.0029, its fp8 control
    # at least 0.0070 / 0.0343 / 0.0130; tier1.max_excess: the program at
    # most 0.0045, the control at least 0.1335 (nine further seeds)
    "checks": {"tier0.max_gap": 0.006, "tier1.max_gap": 0.02,
               "tier1.max_excess": 0.03, "router.max_score_gap": 0.006},
}

MIX = {"name": "tiny", "load": 1.0,
       "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 4,
                  "max": 40},
       "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
                  "max": 16},
       "ids": {"low": 4, "high": 512}, "order_seed": 3, "warmup_s": 1.0,
       "drain_s": 5.0}


def cell():
    from chipbench import spec
    return spec.Cell("tiny.tiny", CONFIG, MIX, 1,
                     [{"name": n, "unit": u} for n, u in (
                         ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"),
                         ("setup_s", "s"))],
                     [])
