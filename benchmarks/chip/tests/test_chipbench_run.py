"""End to end on the CPU, at a tiny size: the harness drives a routed pool
through an open loop and checks what the window served against the plain
references. A clean run is correct; each fault planted under the timed
path, and the fp8 control in the program's place, make it incorrect."""
import time

import pytest

import chipbench_tiny as tiny
from chipbench import cell as cellrun, harness
from chipbench.faults import alter_tokens, flip_routes, freeze_kv, freeze_state

SEED = 2 ** 31 + 12345   # seeds are large in the benchmark's checks


@pytest.fixture(scope="module")
def built():
    c = tiny.cell()
    return c, harness.Run(c.config, SEED)


def serve(built, seed, fault=None, control=None):
    c, run = built
    undo = fault(run) if fault is not None else None
    try:
        out = cellrun.serve_cell(c, seed, 2.0, False, time.monotonic(),
                                 {"compiles": 0, "compile_s": 0.0,
                                  "cache_hits": 0}, run=run)
        while run.pool.has_work:
            run.pool.step()
        checks = cellrun.run_checks(c, out, seed, control=control)
        return cellrun.result(c, out, False, checks)
    finally:
        if undo is not None:
            undo()
        run.reset_pools()


def test_clean_run_is_correct(built):
    line = serve(built, SEED)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault,number", [
    (alter_tokens, "tier1.max_gap"), (freeze_kv, "tier1.max_gap"),
    (alter_tokens, "tier1.max_excess"), (freeze_kv, "tier1.max_excess"),
    (freeze_state, "tier0.max_gap"), (flip_routes, "router.wrong_side")])
def test_fault_under_the_timed_path_is_caught(built, fault, number):
    line = serve(built, SEED + 1, fault=fault)
    assert not line["correct"]
    c = line["checks"][number]
    assert c["value"] > c["limit"], line["checks"]


def test_fp8_control_is_caught(built):
    line = serve(built, SEED + 2, control="fp8")
    assert not line["correct"], line["checks"]
