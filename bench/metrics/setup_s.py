"""Seconds from process start to the first timed request: imports, device
start, weights, compile-cache load or compile, warm-up, and for decode the
filling of the cache."""


def read(run):
    return run.setup_s
