"""Record the window with JAX's profiler and reduce the trace to numbers.

The reduction reads the ``.xplane.pb`` the profiler writes, through
``jax.profiler.ProfileData``:

* device busy time is the union of the intervals of the operations on each
  device's ``XLA Ops`` line, inside the traced window;
* the traced window runs from the first ``dispatch`` span to the end of the
  last ``fetch`` span: the benchmark's own host spans
  (``jax.profiler.TraceAnnotation``), on the trace's clock;
* each idle gap (the window minus the busy union) is labelled by the host
  span it overlaps most (``dispatch``, ``fetch`` or ``other``);
* operations are ranked by exclusive time: a ``while`` op spans the ops of
  its body, which the trace lists on the same line, so nested time counts
  once, for the innermost op;
* busy time per tick is compared between the two halves of the window's
  chunks, which shows whether a tick's cost grows as the caches fill.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("dispatch", "fetch")
NAME_CHARS = 200                   # an HLO op's name in the trace runs long


@dataclasses.dataclass
class TraceSummary:
    busy_s: float                 # mean over the devices used
    window_s: float
    chunks: int                   # chunks dispatched inside the window
    busy_s_halves: tuple          # busy seconds in the first and second half
    half_chunks: tuple            # chunks dispatched in each half
    top_ops: list                 # [[name, seconds], ...] by exclusive device time
    idle_gaps: list               # [[label, seconds], ...] longest first
    devices: int


def _union(intervals):
    """Merge (start, end) intervals; returns the merged, sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(ops, lo, hi) -> dict:
    """Exclusive device time per operation name inside [lo, hi]: an
    operation's duration less that of the operations nested in it (a
    ``while`` op spans the ops of its body on the same line)."""
    totals, stack = {}, []          # stack of [name, start, end, child time]

    def close(entry):
        name, s, e, child = entry
        own = max(0.0, min(e, hi) - max(s, lo)) - child
        totals[name] = totals.get(name, 0.0) + max(own, 0.0)
        if stack:
            stack[-1][3] += max(0.0, min(e, hi) - max(s, lo))

    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return totals


def _clip_total(merged, lo, hi) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def events_of(xspace_path: str):
    """Plain tuples from an ``.xplane.pb``: (device events, host spans).

    device events: {device plane name: [(op name, start_ns, end_ns)]};
    host spans: [(name, start_ns, end_ns)] of the benchmark's own spans.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xspace_path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in HOST_SPANS)
    return devices, spans


def reduce_events(devices: dict, spans: list, top: int = 10) -> TraceSummary:
    """Busy union, idle gaps and top operations over the traced window."""
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operations")
    dispatch = sorted(s for s in spans if s[0] == "dispatch")
    fetch = sorted(s for s in spans if s[0] == "fetch")
    if not dispatch or not fetch:
        raise ValueError("the trace holds no dispatch/fetch host spans")
    lo, hi = dispatch[0][1], fetch[-1][2]
    window_ns = hi - lo
    mid = dispatch[len(dispatch) // 2][1]

    busy, halves, totals = [], [0.0, 0.0], {}
    gaps = []
    for ops in devices.values():
        merged = _union([[s, e] for _, s, e in ops if e > lo and s < hi])
        busy.append(_clip_total(merged, lo, hi))
        halves[0] += _clip_total(merged, lo, mid)
        halves[1] += _clip_total(merged, mid, hi)
        for name, t in _self_times(ops, lo, hi).items():
            totals[name] = totals.get(name, 0.0) + t
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge and edge < hi:
                gaps.append((max(edge, lo), min(s, hi)))
            edge = max(edge, e)
    n_dev = len(devices)
    host = sorted(spans, key=lambda x: x[1])
    labelled = []
    for g0, g1 in gaps:
        best, label = 0.0, "other"
        for name, s, e in host:
            if s >= g1:
                break
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, label = ov, name
        labelled.append([label, (g1 - g0) / 1e9])
    labelled.sort(key=lambda x: -x[1])
    ranked = sorted(totals.items(), key=lambda x: -x[1])[:top]
    return TraceSummary(
        busy_s=sum(busy) / n_dev / 1e9,
        window_s=window_ns / 1e9,
        chunks=len(dispatch),
        busy_s_halves=(halves[0] / n_dev / 1e9, halves[1] / n_dev / 1e9),
        half_chunks=(len(dispatch) // 2, len(dispatch) - len(dispatch) // 2),
        top_ops=[[name[:NAME_CHARS], t / n_dev / 1e9] for name, t in ranked],
        idle_gaps=labelled[:top],
        devices=n_dev,
    )


class Tracer:
    """``jax.profiler`` around the window; the trace lives in a temporary
    directory (under ``TMPDIR``) that ``summary`` reads and removes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans stay; Python calls go
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def xspace_path(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return max(found, key=os.path.getmtime)

    def summary(self) -> TraceSummary:
        try:
            return reduce_events(*events_of(self.xspace_path()))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
