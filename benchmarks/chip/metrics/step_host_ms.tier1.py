"""step_host_ms.tier1: mean host time of tier 1's engine steps (the
program's ``engine.step`` span inside the harness's ``tier1.step``) that
start in the traced window, less what their ``*.sync`` spans cover: the
Python a step runs between and around its launches (phases.step_host_ms)."""
from chipbench import readings


def read(rec):
    ph = readings.phases(rec)
    return ph["step_host_ms"].get("tier1") if ph else None
