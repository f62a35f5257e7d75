"""CPU tests of the phase reduction (``chipbench.phases``): a synthetic
trace checked by hand, and a profiled run of the tiny pool whose spans must
nest as the program opens them."""
import glob
import math

import jax
import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import harness, phases, tracing

MS = 1_000_000


def synthetic():
    """A tier-1 step with its phases, a router call, and three device ops;
    the window is [0, 100] ms. Times in ms."""
    host = [("window", 0, 100), ("tier1.step", 10, 60),
            ("engine.step", 12, 58), ("engine.admit#admitted=1#", 13, 15),
            ("engine.prefill", 15, 20), ("engine.prefill.sync", 16, 19),
            ("engine.decode.prep", 20, 30),
            ("engine.decode.dispatch", 30, 32),
            ("engine.decode.sync", 32, 50), ("engine.decode.emit", 50, 56),
            ("PjitFunction(decode_step)", 30, 31),
            ("router", 62, 68), ("router.decide", 62.5, 67.5),
            ("router.sync", 65, 67), ("wait", -10, 3)]
    ops = [("fusion.1", 0, 5), ("paged_decode_attention.1", 31, 49),
           ("fusion.2", 70, 80)]
    scale = lambda evs: [(n, s * MS, e * MS) for n, s, e in evs]
    return scale(ops), scale(host)


def test_phase_reduce_by_hand():
    ops, host = synthetic()
    ph = phases.reduce(ops, host, tracing.window_of(host))
    # idle: [5, 31], [49, 70], [80, 100]; each piece goes to the innermost
    # span over it
    want = {"host:idle": 5 + 2 + 2 + 20, "tier1.step": 2 + 2,
            "engine.step": 1 + 2, "engine.admit": 2, "engine.prefill": 2,
            "engine.prefill.sync": 3, "engine.decode.prep": 10,
            "engine.decode.dispatch": 1, "engine.decode.sync": 1,
            "engine.decode.emit": 6, "router": 1, "router.decide": 3,
            "router.sync": 2}
    assert ph["idle_by_span"] == pytest.approx(
        {n: v / 1e3 for n, v in want.items()})
    assert ph["step_idle_s"] == pytest.approx(0.028)   # [12, 31] + [49, 58]
    assert phases.step_idle_share(ph) == pytest.approx(28.0)
    assert ph["idle_gaps"] == [
        ("during engine.prefill.sync", pytest.approx(0.026)),
        ("during tier1.step", pytest.approx(0.021)),
        ("during host:idle", pytest.approx(0.020))]
    spans = ph["spans"]
    # the engine step: 46 ms, of which its two sync children cover 21
    assert spans[("tier1.step", "engine.step")] == pytest.approx(
        {"count": 1, "total_s": 0.046, "sync_s": 0.021})
    assert spans[(None, "tier1.step")]["sync_s"] == pytest.approx(0.021)
    assert spans[("tier1.step", "engine.prefill")]["sync_s"] == \
        pytest.approx(0.003)
    assert spans[("tier1.step", "engine.admit")]["count"] == 1
    assert spans[(None, "router.decide")] == pytest.approx(
        {"count": 1, "total_s": 0.005, "sync_s": 0.002})
    # "wait" starts before the window; the window span and the dispatch
    # event are not spans of the program or the harness
    assert (None, "wait") not in spans and (None, "window") not in spans
    assert not any(n.startswith("PjitFunction") for _, n in spans)
    assert phases.step_host_ms(ph, 1) == pytest.approx(25.0)
    assert phases.step_host_ms(ph, 0) is None


def test_phase_reduce_agrees_with_the_trace_reduction():
    """The phase reduction cuts the same idle time into pieces: it sums to
    what ``tracing.reduce`` leaves idle, over gaps of the same lengths."""
    ops, host = synthetic()
    window = tracing.window_of(host)
    ph = phases.reduce(ops, host, window)
    tr = tracing.reduce(ops, host, window)
    assert sum(ph["idle_by_span"].values()) == pytest.approx(
        tr["window_s"] - tr["busy_s"])
    assert [v for _, v in ph["idle_gaps"]] == \
        pytest.approx([v for _, v in tr["idle_gaps"]])
    assert ph["window_s"] == tr["window_s"]


def test_cut_span_stays_inside_its_parent():
    host = [("engine.step", 0, 10), ("engine.decode.emit", 8, 12),
            ("engine.step", 12, 20)]
    spans = phases.nest(host)
    assert spans[1] == ("engine.decode.emit", 8, 10, 0)
    assert spans[2][3] is None
    assert phases.innermost(spans) == [(0, 8, 0), (8, 10, 1), (12, 20, 2)]


# ---------------------------------------------------- a profiled tiny pool
PHASES = ("engine.expire", "engine.admit", "engine.prefill",
          "engine.decode.prep", "engine.decode.dispatch",
          "engine.decode.sync", "engine.decode.emit")


def ancestors(spans, i):
    p = spans[i][3]
    while p is not None:
        yield spans[p][0]
        p = spans[p][3]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The tiny pool serving six prompts to the end under the profiler,
    half of them routed to each tier."""
    c = tiny.cell()
    run = harness.Run(c.config, 2 ** 31 + 777)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(4, 512, (n,)).astype(np.int32)
               for n in (9, 14, 20, 11, 17, 6)]
    run.calibrate(prompts, 0.5)
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            tiers = []
            for p in prompts:
                row, mask = harness.padded(p)
                _, t, _ = run.pool.submit(row, mask,
                                          max_new_tokens=np.asarray([3]),
                                          temperature=0.0)
                tiers.append(int(t[0]))
            for _ in range(64):
                if not run.pool.has_work:
                    break
                run.pool.step()
    assert not run.pool.has_work and set(tiers) == {0, 1}
    files = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
    device, host, _ = tracing.load(files[0])
    return device, host


def test_program_spans_nest_in_a_profiled_run(traced):
    device, host = traced
    spans = phases.nest(host)
    names = {n for n, *_ in spans}
    missing = set(PHASES + ("engine.step", "engine.prefill.dispatch",
                            "engine.prefill.sync", "router.decide",
                            "router.sync", "pool.enqueue", "pool.account"))
    assert not missing - names, missing - names
    for i, (n, *_) in enumerate(spans):
        up = list(ancestors(spans, i))
        if n in PHASES:
            assert up[0] == "engine.step", (n, up)
        if n in ("engine.prefill.dispatch", "engine.prefill.sync"):
            assert up[0] == "engine.prefill", (n, up)
        if n == "engine.step":
            assert phases.TIER_STEP.fullmatch(up[0]), up
        if n == "router.sync":
            assert up[:2] == ["router.decide", "router"], up
    ph = phases.reduce(device, host, tracing.window_of(host))
    for value in (phases.step_host_ms(ph, 0), phases.step_host_ms(ph, 1),
                  phases.step_idle_share(ph)):
        assert value is not None and math.isfinite(value)
    assert 0 < phases.step_idle_share(ph) <= 100
    assert set(phases.summary(ph)["step_host_ms"]) == {"tier0", "tier1"}
