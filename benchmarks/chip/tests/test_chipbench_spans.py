"""CPU tests of the trace record a traced run keeps: the program's spans
reduced beside the device ops (``cell.reduce_events``), idle gaps named
after the innermost program span, and the readers of that record."""
import pytest

import chipbench_tiny  # noqa: F401  (puts chipbench on the path)
from chipbench import cell, spec
from test_chipbench_phases import synthetic

READERS = [{"name": n, "unit": u} for n, u in (
    ("step_host_ms.tier0", "ms"), ("step_host_ms.tier1", "ms"),
    ("step_idle_share.chat", "%"))]


def test_trace_record_holds_the_phase_reduction():
    ops, host = synthetic()
    tr = cell.reduce_events(ops, host, {"fusion.1": "fusion.1 k=v"})
    assert tr["busy_s"] == pytest.approx(0.033)
    assert tr["labels"]["fusion.1"] == "fusion.1 k=v"
    assert tr["labels"]["fusion.2"] == "fusion.2"
    ph = tr["phases"]
    assert ph["step_host_ms"] == {"tier1": pytest.approx(25.0)}
    assert ph["step_idle_share"] == pytest.approx(28.0)
    idle = list(ph["idle_by_span"].items())
    assert idle[0] == ("host:idle", pytest.approx(0.029))
    assert [v for _, v in idle] == sorted((v for _, v in idle), reverse=True)
    assert sum(v for _, v in idle) == pytest.approx(
        tr["window_s"] - tr["busy_s"])
    step = [r for r in ph["spans"] if r["span"] == "engine.step"]
    assert step == [{"tier": "tier1.step", "span": "engine.step",
                     "count": 1, "total_s": pytest.approx(0.046),
                     "sync_s": pytest.approx(0.021)}]
    # the breakdown names each idle gap after the innermost span, the
    # program's where one is open
    assert tr["idle_gaps"] == [
        ("during engine.prefill.sync", pytest.approx(0.026)),
        ("during tier1.step", pytest.approx(0.021)),
        ("during host:idle", pytest.approx(0.020))]


def test_span_readers_read_the_trace_record():
    ops, host = synthetic()
    rec = {"trace": cell.reduce_events(ops, host, {})}
    # no tier-0 step in the window: that reader finds nothing
    assert spec.read_metrics(READERS, rec) == {
        "step_host_ms.tier1": {"value": pytest.approx(25.0), "unit": "ms"},
        "step_idle_share.chat": {"value": pytest.approx(28.0), "unit": "%"}}
    assert spec.read_metrics(READERS, {"trace": None}) == {}
