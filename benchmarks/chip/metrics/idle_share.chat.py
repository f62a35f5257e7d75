"""idle_share.chat: share of the traced window in which no operation ran
on the device (1 minus the union of device-op intervals)."""
from chipbench import readings


def read(rec):
    return readings.idle_share(rec)
