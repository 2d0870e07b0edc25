"""setup_s: process start to the first timed request: TPU client start,
data generation, write, ingest, the index build where there is one, and
warm-up (host clock)."""


def read(win):
    return win.setup_s
