"""setup_s: process start to window start, compilation, warm-up and
the un-timed open loop included (host clock)."""


def read(rec):
    return rec["setup_s"]
