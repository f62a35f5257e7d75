"""queue_wait_p95_ms: 95th percentile of the program's first-admission
stamp (Request.start_t) minus due time, over every request due in the
window; one never admitted stands at due time to the end of the drain."""
from chipbench import readings


def read(rec):
    waits = [(r["start"] if r["start"] else rec["drain_end"]) - r["due"]
             for r in readings.due_in_window(rec)]
    return readings.p95_ms(waits)
