"""itl_p95_ms: 95th percentile of every gap between consecutive output
tokens emitted in the window, both tiers (host clock)."""
from chipbench import readings


def read(rec):
    return readings.p95_ms(readings.gaps_s(rec))
