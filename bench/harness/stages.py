"""Put the traced window's device time and idle gaps down to what owns them.

``trace.reduce_events`` gives one busy union for the whole chunk program and
labels an idle gap only by the benchmark's own host span.  This module splits
both further:

* stages: the fused tick names its sections with ``jax.named_scope``
  (``stage.plan``, ``stage.probe``, ``stage.sweep``, ``stage.upsert``,
  ``stage.writer``), and the scope survives into the ``op_name`` metadata of
  the compiled chunk program.  A trace names each device op by its
  instruction text (``%copy.350 = ...``) and carries no metadata, so the map
  from op to stage is read from the compiled program's text
  (``compiled.as_text()``): an instruction belongs to the innermost
  ``stage.<x>`` of its ``op_name``, else to ``unscoped`` (the scan's row
  aggregation, loop control, the carry copies of the entry computation).  An
  op of the trace that the text does not hold raises: the map came from
  another program.  The window's exclusive op times (``trace._self_times``)
  summed by stage add up to the busy union;
* idle gaps: the gaps of ``trace.reduce_events`` (same lengths, same order),
  each labelled ``<span>/<event>``: the benchmark span it falls in
  (``dispatch``, ``fetch`` or ``other``) and the runtime's host event whose
  own time overlaps it most, summed over the host threads (an event's own
  time is where it is the innermost event open on its thread, so a nest is
  put down to the call that does the work, not to the call around it), or
  ``none``;
* the runtime's own host spans: its execute call, once per chunk, and its
  device-to-host transfers, one per field of a chunk's metrics row.

Events of the profiler itself (Python tracer calls, named ``$...``) and the
benchmark's own spans are not runtime events.
"""
from __future__ import annotations

import dataclasses
import re

from harness import trace

STAGES = ("plan", "probe", "sweep", "upsert", "writer", "unscoped")
EXECUTE = "PJRT_LoadedExecutable_Execute"     # the runtime's launch of a chunk
TRANSFER = "tpu::System::TransferFromDevice"  # one device-to-host transfer
TOP_PER_STAGE = 3

_SCOPE = re.compile(r"stage\.(" + "|".join(STAGES[:-1]) + r")\b")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_EVENT = re.compile(r"^%?([^\s=]+) = ")


@dataclasses.dataclass
class StageSummary:
    stage_s: dict       # stage -> device seconds, for stages that own an op
    top_ops: dict       # stage -> [[instruction, computation, seconds], ...]
    idle_gaps: list     # [[span/event, seconds], ...] longest first
    launch_s: float     # the runtime's execute spans inside the window
    launches: int
    transfers: int      # device-to-host transfers inside the window
    chunks: int         # chunks dispatched inside the window


def op_stages(hlo_text: str) -> dict:
    """{instruction name: (stage, computation)} of a compiled program's text;
    the computation is ``ENTRY`` or the name of the one that holds it."""
    out, comp = {}, None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            scopes = _SCOPE.findall(" ".join(_OP_NAME.findall(m.group(2))))
            out[m.group(1)] = (scopes[-1] if scopes else "unscoped", comp)
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = "ENTRY" if m.group(1) else m.group(2)
    return out


def instruction_of(event_name: str) -> str:
    m = _EVENT.match(event_name)
    if not m:
        raise ValueError(f"device event {event_name[:80]!r} names no HLO "
                         "instruction")
    return m.group(1)


def events(xspace_path: str):
    """(device events, benchmark spans, runtime host events) of an
    ``.xplane.pb``: the first two as ``trace.events_of`` gives them, the
    third as {host thread: [(name, start_ns, end_ns)]}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xspace_path)
    devices, spans, runtime = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            devices[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == trace.OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread = runtime.setdefault(f"{plane.name}/{line.name}", [])
                for e in line.events:
                    ev = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name in trace.HOST_SPANS:
                        spans.append(ev)
                    elif not e.name.startswith("$"):
                        thread.append(ev)
    return devices, spans, runtime


def _window(spans):
    dispatch = sorted(s for s in spans if s[0] == "dispatch")
    fetch = sorted(s for s in spans if s[0] == "fetch")
    if not dispatch or not fetch:
        raise ValueError("the trace holds no dispatch/fetch host spans")
    return dispatch[0][1], fetch[-1][2], len(dispatch)


def stage_times(devices: dict, spans: list, ops: dict):
    """({stage: seconds}, {stage: top ops}) over the window, mean over the
    devices; a stage with no op in the window is left out."""
    lo, hi, _ = _window(spans)
    n_dev = len(devices)
    per_op = {}
    for dev_ops in devices.values():
        inside = [o for o in dev_ops if o[2] > lo and o[1] < hi]
        for name, t in trace._self_times(inside, lo, hi).items():
            key = instruction_of(name)
            if key not in ops:
                raise KeyError(f"device op {key!r} is not in the compiled "
                               "program's text: the map is of another program")
            per_op[key] = per_op.get(key, 0.0) + t / n_dev
    stage_ns, top = {}, {}
    for key, t in sorted(per_op.items(), key=lambda x: -x[1]):
        stage, comp = ops[key]
        stage_ns[stage] = stage_ns.get(stage, 0.0) + t
        if len(top.setdefault(stage, [])) < TOP_PER_STAGE:
            top[stage].append([key, comp, t / 1e9])
    return {s: t / 1e9 for s, t in stage_ns.items()}, top


def labelled_gaps(devices: dict, spans: list, runtime: dict, top: int = 10):
    """The idle gaps of ``trace.reduce_events``, labelled ``<span>/<event>``."""
    lo, hi, _ = _window(spans)
    gaps = []
    for dev_ops in devices.values():
        merged = trace._union([[s, e] for _, s, e in dev_ops if e > lo and s < hi])
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge and edge < hi:
                gaps.append((max(edge, lo), min(s, hi)))
            edge = max(edge, e)
    gaps.sort(key=lambda g: g[0] - g[1])      # stable, as reduce_events sorts
    host = sorted(spans, key=lambda x: x[1])
    own = sorted((seg for evs in runtime.values() for seg in _own_time(evs)),
                 key=lambda x: x[1])
    return [[f"{_span(host, g0, g1)}/{_most(own, g0, g1)}", (g1 - g0) / 1e9]
            for g0, g1 in gaps[:top]]


def _span(host, g0, g1):
    """The benchmark span that overlaps [g0, g1] most, as reduce_events
    labels a gap."""
    best, label = 0.0, "other"
    for name, s, e in host:
        if s >= g1:
            break
        ov = min(e, g1) - max(s, g0)
        if ov > best:
            best, label = ov, name
    return label


def _own_time(thread_events):
    """[(name, start, end)]: the stretches in which each event is the
    innermost one open on its thread (the events of one thread nest)."""
    out, stack, cur = [], [], 0

    def close():
        name, _, end = stack.pop()
        if end > cur:
            out.append((name, cur, end))
        return max(cur, end)

    for name, s, e in sorted(thread_events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            cur = close()
        if stack and s > cur:
            out.append((stack[-1][0], cur, s))
        stack.append((name, s, e))
        cur = s
    while stack:
        cur = close()
    return out


def _most(own, g0, g1):
    """The runtime event whose own time overlaps [g0, g1] most in all."""
    overlap = {}
    for name, s, e in own:
        if s >= g1:
            break
        if e > g0:
            overlap[name] = overlap.get(name, 0.0) + min(e, g1) - max(s, g0)
    if not overlap:
        return "none"
    return max(overlap.items(), key=lambda x: (x[1], x[0]))[0]


def reduce(devices: dict, spans: list, runtime: dict, ops: dict) -> StageSummary:
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operations")
    lo, hi, chunks = _window(spans)
    stage_s, top = stage_times(devices, spans, ops)
    flat = [ev for evs in runtime.values() for ev in evs]
    launches = [e - s for name, s, e in flat if name == EXECUTE and lo <= s < hi]
    transfers = sum(1 for name, s, _ in flat if name == TRANSFER and lo <= s < hi)
    return StageSummary(stage_s=stage_s, top_ops=top,
                        idle_gaps=labelled_gaps(devices, spans, runtime),
                        launch_s=sum(launches) / 1e9, launches=len(launches),
                        transfers=transfers, chunks=chunks)


def stage_ms_per_tick(run, stage: str):
    """A stage's device ms per simulated tick over the window's chunks, or
    None where the run has no stage summary or the stage owns no op."""
    ss = getattr(run, "stage_summary", None)
    if ss is None or not ss.chunks or stage not in ss.stage_s:
        return None
    return ss.stage_s[stage] * 1e3 / (ss.chunks * run.cell.chunk_ticks)


def breakdown(ss: StageSummary, chunk_ticks: int) -> dict:
    """``breakdown.stages`` of a result line: per stage its ms per tick and
    its longest ops by exclusive time, with their computation."""
    per_tick = 1e3 / (ss.chunks * chunk_ticks)
    return {s: {"ms_per_tick": ss.stage_s[s] * per_tick,
                "top_ops": [[op, comp, t * per_tick] for op, comp, t in ss.top_ops[s]]}
            for s in STAGES if s in ss.stage_s}
