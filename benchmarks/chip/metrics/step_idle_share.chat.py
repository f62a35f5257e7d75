"""step_idle_share.chat: share of the traced window in which no operation
runs on the device while the host is inside some engine step (the
program's ``engine.step`` span; phases.step_idle_share)."""
from chipbench import readings


def read(rec):
    ph = readings.phases(rec)
    return ph["step_idle_share"] if ph else None
