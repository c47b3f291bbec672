"""Device busy time (the union of operation intervals) per simulated tick
over the chunks dispatched inside the traced window."""


def read(run):
    ts = run.trace_summary
    if ts is None or ts.chunks == 0:
        return None
    return ts.busy_s * 1e3 / (ts.chunks * run.cell.chunk_ticks)
