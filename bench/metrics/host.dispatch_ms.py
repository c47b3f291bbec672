"""Mean host time per chunk from the chunk call until it returns, before the
row is fetched: tracing, argument handling and the launch on the host."""


def read(run):
    spans = [t1 - t0 for name, t0, t1 in run.spans if name == "dispatch"]
    return sum(spans) / len(spans) * 1e3 if spans else None
