"""Seconds from process start to the first timed chunk: imports, device
start-up, the fog's initial state, compile or compile-cache load, warm-up."""


def read(run):
    return run.setup_s
