"""ttft_p95_ms: 95th percentile of due time to first token over every
request due in the window; one never answered ranks last, at due time to
the end of the drain (host clock)."""
from chipbench import readings


def read(rec):
    return readings.p95_ms(readings.ttft_s(rec))
