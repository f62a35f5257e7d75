"""CPU tests of the benchmark's own arithmetic: trace reduction, work
formulas, traffic generation, discovery by name, and the refusal to run
without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chipbench_tiny as tiny  # noqa: F401  (puts chipbench on the path)
from chipbench import readings, spec, stats, tracing, traffic, work

CHIP = tiny.CHIP


# ------------------------------------------------------------ trace reduce
def test_reduce_busy_union_kernels_and_gaps():
    ms = 1_000_000
    ops = [("fusion.1", 0, 10 * ms), ("_paged_kernel", 5 * ms, 20 * ms),
           ("_paged_kernel", 30 * ms, 40 * ms), ("copy", 90 * ms, 120 * ms)]
    host = [("tier1.step", 19 * ms, 31 * ms), ("wait", 40 * ms, 95 * ms),
            ("window", 0, 100 * ms)]
    out = tracing.reduce(ops, host, tracing.window_of(host))
    assert out["window_s"] == pytest.approx(0.1)
    # union: [0, 20] + [30, 40] + [90, 100] (clipped to the window)
    assert out["busy_s"] == pytest.approx(0.040)
    assert out["op_s"]["_paged_kernel"] == pytest.approx(0.025)
    assert out["op_s"]["copy"] == pytest.approx(0.010)
    assert tracing.kernel_seconds(out["op_s"], "_paged_kernel") == \
        pytest.approx(0.025)
    # gaps: [20, 30] inside tier1.step, [40, 90] inside wait
    assert out["idle_gaps"] == [("during wait", pytest.approx(0.05)),
                                ("during tier1.step", pytest.approx(0.01))]
    assert out["device_ops"][0][0] == "_paged_kernel"


def test_merge_overlapping_and_nested():
    assert tracing.merge([(5, 9), (0, 3), (2, 4), (6, 7), (10, 10)]) == \
        [(0, 4), (5, 9)]


# ------------------------------------------------------------------ work
MISTRAL = {"d_model": 12288, "n_heads": 96, "n_kv_heads": 8, "head_dim": 128,
           "d_ff": 28672, "n_layers": 2, "vocab_size": 32768}


def test_paged_decode_work_by_hand():
    f, b = work.paged_decode_work(MISTRAL, [100, 300])
    # QK^T and PV: 2 flops x 2 matmuls x keys x 96 heads x 128, 2 layers
    assert f == 4 * 400 * 96 * 128 * 2
    # K and V of every key for 8 KV heads, plus q in and o out, bf16
    assert b == ((2 * 400 * 8 * 128) + 2 * (2 * 96 * 128)) * 2 * 2


def test_paged_prefill_work_by_hand():
    f, b = work.paged_prefill_work(MISTRAL, [(0, 2), (10, 1)])
    # causal keys: (1 + 2) for the first chunk, 11 for the second
    assert f == 4 * 96 * 128 * (3 + 11) * 2
    assert b == ((2 * 2 * 8 * 128 + 2 * 2 * 96 * 128)
                 + (2 * 11 * 8 * 128 + 2 * 1 * 96 * 128)) * 2 * 2


def test_model_flops_and_roofline_by_hand():
    per_layer = 12288 * 96 * 128 * 2 + 2 * 12288 * 8 * 128 + 3 * 12288 * 28672
    assert work.decoder_layer_params(MISTRAL) == per_layer
    assert work.token_flops("decoder", MISTRAL, 50) == \
        2 * per_layer * 2 + 4 * 50 * 96 * 128 * 2
    assert work.prefill_flops("decoder", MISTRAL, 0, 3) == \
        3 * work.token_flops("decoder", MISTRAL, 2)
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 200 flops take 2 s, 10 bytes 1 s: compute-bound at 2 s of 4 s
    assert work.roofline_share(200, 10, 4.0, peaks) == 50.0


def test_mfu_reads_work_over_step_time():
    m = {"d_model": 4, "n_heads": 1, "n_kv_heads": 1, "head_dim": 4,
         "d_ff": 8, "n_layers": 1, "vocab_size": 10}
    rec = {"window": [0.0, 10.0], "requests": [
               {"submit": 1.0, "prompt_len": 3, "tier": 0,
                "token_t": [2.0, 3.0]}],
           "router": {"d_model": 2, "d_ff": 4, "n_layers": 1},
           "tiers": [{"kind": "decoder", "model": m}],
           "work": {"tier0.prefill": [(1.5, [(0, 3)])],
                    "tier0.decode": [(2.5, [4])]},
           "spans": {"router": [(1.0, 1.5)], "tier0": [(1.5, 2.0),
                                                       (2.5, 3.0)]},
           "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    flops = (work.router_flops(rec["router"], 3)
             + work.prefill_flops("decoder", m, 0, 3)
             + work.token_flops("decoder", m, 4)
             + 2 * work.lm_head_flops(m))
    assert readings.model_flops(rec) == pytest.approx(flops)
    assert readings.mfu(rec) == pytest.approx(100.0 * flops / 1.5)


def test_percentile_ranks_missing_last():
    assert stats.percentile([3, 1, 2, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([1.0, float("inf")], 100) == float("inf")


def test_bf16_step_bounds_the_gap_of_a_tie():
    import ml_dtypes
    from chipbench import harness
    assert harness.bf16_step(np.float32(4.5)) == 2.0 ** -5
    assert harness.bf16_step(np.float32(-0.75)) == 2.0 ** -8
    # greedy over bf16 logits: a lower logit can win only by a tie, and
    # tied logits lie less than one step of the larger apart
    rng = np.random.default_rng(0)
    a = rng.normal(0.0, 2.0, 100_000).astype(np.float32)
    b = (a + rng.uniform(0.0, 0.05, a.shape)).astype(np.float32)
    ra, rb = (x.astype(ml_dtypes.bfloat16).astype(np.float32) for x in (a, b))
    assert np.all(ra <= rb)
    tie = ra == rb
    assert tie.any()
    assert np.all((b - a)[tie] <= harness.bf16_step(np.maximum(
        np.abs(a), np.abs(b)))[tie])


# --------------------------------------------------------------- traffic
def test_schedule_is_the_mix_s_own():
    mix = spec.load_mix("chat")
    a = traffic.schedule(mix, 2.0, 30.0)
    b = traffic.schedule(mix, 2.0, 30.0)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    d = traffic.schedule({**mix, "order_seed": mix["order_seed"] + 1},
                         2.0, 30.0)
    for seg in traffic.SEGMENTS:
        sa = [x for x in a if x.segment == seg]
        sd = [x for x in d if x.segment == seg]
        assert sorted(len(x.tokens) for x in sa) == \
            sorted(len(x.tokens) for x in sd)
        assert sorted(x.out_cap for x in sa) == sorted(x.out_cap for x in sd)
    assert [len(x.tokens) for x in a] != [len(x.tokens) for x in d]
    win = [x for x in a if x.segment == "window"]
    assert len(win) == 60
    assert min(x.due_s for x in win) == pytest.approx(mix["warmup_s"])
    assert max(x.due_s for x in win) < mix["warmup_s"] + 30.0
    lens = [len(x.tokens) for x in a]
    assert min(lens) >= mix["prompt"]["min"]
    assert max(lens) <= mix["prompt"]["max"]
    ids = np.concatenate([x.tokens for x in a])
    assert ids.min() >= mix["ids"]["low"] and ids.max() < mix["ids"]["high"]


def test_quantile_lengths_follow_the_median():
    spec_ = {"dist": "lognormal", "median": 512, "sigma": 1.0, "min": 32,
             "max": 4096}
    lens = traffic.quantile_lengths(spec_, 1001)
    assert np.median(lens) == 512
    assert lens.min() >= 32 and lens.max() == 4096


# --------------------------------------------------------------- by name
def test_cells_configs_mixes_and_metrics_are_found_by_name():
    bench = spec.load_benchmark()
    metrics = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["name"] == w["traffic"]
        names = {m["name"] for m in cell.end_to_end + cell.per_layer}
        assert "setup_s" in names and names <= metrics
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for name in metrics:
        assert callable(spec.metric_reader(name))
    for c in bench["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
    with pytest.raises(KeyError):
        spec.load_peaks("no such chip")
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_reader_that_finds_nothing_is_left_out():
    rec = {"trace": None, "work": None, "peaks": None}
    out = spec.read_metrics([{"name": "idle_share.chat", "unit": "%"},
                             {"name": "paged_decode_roofline", "unit": "%"}],
                            rec)
    assert out == {}


# ---------------------------------------------------------------- no TPU
def test_run_refuses_a_backend_that_is_not_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    bench = spec.load_benchmark()
    p = subprocess.run([sys.executable, str(CHIP / "run.py"), "--workload",
                        bench["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
