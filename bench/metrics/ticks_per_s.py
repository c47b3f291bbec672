"""Simulated ticks completed in the window over the window's wall time (host
clock; each chunk ends when its metrics row is on the host)."""


def read(run):
    return run.ticks / run.window_s if run.window_s > 0 else None
