"""router.ms_per_request: host time of the pool's policy.decide calls that
started in the window (each scores one request and ends on the host),
per request scored."""
from chipbench import readings


def read(rec):
    spans = readings.in_window(rec["spans"]["router"], rec)
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
