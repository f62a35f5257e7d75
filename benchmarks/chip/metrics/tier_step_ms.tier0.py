"""tier_step_ms.tier0: mean host time of tier 0's engine step, as the
pool calls it, over the steps that started in the window (each step ends
on the host reading its sampled tokens)."""
from chipbench import readings


def read(rec):
    spans = readings.in_window(rec["spans"].get("tier0", []), rec)
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
