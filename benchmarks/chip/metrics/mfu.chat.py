"""mfu.chat: model FLOPs of all work in the traced window (router,
prefill, decode and LM head, both tiers, attention included) over the
summed wall time of the tier steps and router calls in it, times the
chip's bf16 peak."""
from chipbench import readings


def read(rec):
    return readings.mfu(rec)
