"""The stage reduction: the op-to-stage map read from a compiled program's
text, device time per stage, and idle gaps put down to the runtime's host
events; on synthetic events and on a trace recorded on the chip with the
program's text beside it."""
import gzip
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import stages, trace

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "ycsb_a_n500_3chunks"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
SCOPED = ("plan", "probe", "sweep", "upsert", "writer")

HLO = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(f)/while/body/stage.upsert/neg"}
}

%body.2 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %fusion.3 = f32[4]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/stage.writer/stage.probe/add" source_file="f.py" source_line=3}
  %copy.4 = f32[4]{0} copy(%fusion.3)
  ROOT %tuple.5 = (s32[], f32[4]{0}) tuple(%arg, %copy.4)
}

ENTRY %main.6 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.7 = (s32[], f32[4]{0}) while(%a), condition=%cond, body=%body.2, metadata={op_name="jit(f)/while"}
  ROOT %copy.8 = f32[4]{0} copy(%a), metadata={op_name="jit(f)/vmap(stage.plan)/copy"}
}
"""
DEVICE = "/device:TPU:0"
SPANS = [("dispatch", 0, 5), ("fetch", 5, 150)]


def _op(name, s, e):
    return (f"%{name} = f32[4]{{0}} op()", s, e)


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_op_map_takes_the_innermost_scope():
    ops = stages.op_stages(HLO)
    assert ops["fusion.3"] == ("probe", "body.2")       # innermost of two
    assert ops["copy.8"] == ("plan", "ENTRY")           # under a transform
    assert ops["neg.1"] == ("upsert", "fused_computation.1")


def test_op_without_a_stage_is_unscoped():
    ops = stages.op_stages(HLO)
    assert ops["copy.4"] == ("unscoped", "body.2")      # no metadata
    assert ops["while.7"] == ("unscoped", "ENTRY")      # a scope of no stage


def test_stage_times_partition_the_busy_union():
    devices = {DEVICE: [_op("while.7", 0, 100), _op("fusion.3", 10, 40),
                        _op("copy.4", 50, 60), _op("copy.8", 100, 120)]}
    stage_s, top = stages.stage_times(devices, SPANS, stages.op_stages(HLO))
    assert stage_s == pytest.approx({"probe": 30e-9, "plan": 20e-9,
                                     "unscoped": 70e-9}, rel=1e-12)
    assert sum(stage_s.values()) == pytest.approx(
        trace.reduce_events(devices, SPANS).busy_s, rel=1e-12)
    assert top["unscoped"][0] == ["while.7", "ENTRY", pytest.approx(60e-9)]
    assert "sweep" not in stage_s


def test_an_op_the_text_does_not_hold_raises():
    devices = {DEVICE: [_op("copy.8", 0, 10), _op("copy.99", 10, 20)]}
    with pytest.raises(KeyError, match="copy.99"):
        stages.stage_times(devices, SPANS, stages.op_stages(HLO))


def test_gap_goes_to_the_innermost_runtime_event():
    devices = {DEVICE: [_op("copy.8", 0, 1), _op("copy.4", 9, 140)]}
    runtime = {"main": [("Execute", 0, 10), ("AllocateRawBuffer", 2, 8)],
               "worker": [("Transfer", 140, 149)]}
    gaps = stages.labelled_gaps(devices, SPANS, runtime)
    assert gaps == [["fetch/Transfer", pytest.approx(10e-9)],
                    ["dispatch/AllocateRawBuffer", pytest.approx(8e-9)]]
    assert [g[1] for g in gaps] == [
        g[1] for g in trace.reduce_events(devices, SPANS).idle_gaps]


def test_readers_of_a_run_without_a_stage_summary_read_nothing():
    run = SimpleNamespace(trace_summary=None, cell=SimpleNamespace(chunk_ticks=2))
    for name in [f"stage.{s}_ms_per_tick" for s in stages.STAGES] + [
            "host.launch_ms", "host.fetch_transfers_per_chunk"]:
        assert _reader(name)(run) is None


def test_readers_per_tick_and_per_chunk():
    ss = stages.StageSummary(stage_s={"probe": 0.03, "unscoped": 0.01},
                             top_ops={}, idle_gaps=[], launch_s=0.006,
                             launches=3, transfers=72, chunks=3)
    run = SimpleNamespace(stage_summary=ss, cell=SimpleNamespace(chunk_ticks=2))
    assert _reader("stage.probe_ms_per_tick")(run) == pytest.approx(5.0)
    assert _reader("stage.sweep_ms_per_tick")(run) is None
    assert _reader("host.launch_ms")(run) == pytest.approx(2.0)
    assert _reader("host.fetch_transfers_per_chunk")(run) == 24


@pytest.fixture(scope="module")
def recorded():
    devices, spans, runtime = stages.events(str(RECORDED) + ".xplane.pb")
    with gzip.open(str(RECORDED) + ".hlo.txt.gz", "rt") as f:
        text = f.read()
    return devices, spans, runtime, stages.op_stages(text)


def test_recorded_every_stage_owns_op_time(recorded):
    ss = stages.reduce(*recorded)
    for stage in SCOPED:
        assert ss.stage_s.get(stage, 0.0) > 0, stage


def test_recorded_stage_sums_are_the_busy_union(recorded):
    devices, spans, _, _ = recorded
    ss = stages.reduce(*recorded)
    busy = trace.reduce_events(devices, spans).busy_s
    assert set(ss.stage_s) <= set(stages.STAGES)
    assert sum(ss.stage_s.values()) == pytest.approx(busy, rel=1e-9)


def test_recorded_ops_are_all_in_the_text(recorded):
    devices, _, _, ops = recorded
    names = {stages.instruction_of(n) for evs in devices.values() for n, _, _ in evs}
    assert names and names <= set(ops)


def test_recorded_gaps_keep_lengths_and_name_a_runtime_event(recorded):
    devices, spans, runtime, _ = recorded
    gaps = stages.labelled_gaps(devices, spans, runtime)
    assert [g[1] for g in gaps] == [
        g[1] for g in trace.reduce_events(devices, spans).idle_gaps]
    for label, _ in gaps:
        span, event = label.split("/", 1)
        assert span in ("dispatch", "fetch", "other") and event != "none", label


def test_recorded_runtime_spans_per_chunk(recorded):
    ss = stages.reduce(*recorded)
    assert ss.chunks == 3 and ss.launches == 3 and ss.launch_s > 0
    assert ss.transfers == 24 * ss.chunks     # one per TickMetrics field


def test_older_trace_gaps_keep_lengths_and_order():
    devices, spans, runtime = stages.events(
        str(DATA / "paper_stream_3chunks.xplane.pb"))
    gaps = stages.labelled_gaps(devices, spans, runtime)
    assert [g[1] for g in gaps] == [
        g[1] for g in trace.reduce_events(devices, spans).idle_gaps]


def test_a_trace_of_another_program_raises(recorded):
    _, _, _, ops = recorded
    devices, spans, runtime = stages.events(
        str(DATA / "paper_stream_3chunks.xplane.pb"))
    with pytest.raises(KeyError, match="another program"):
        stages.reduce(devices, spans, runtime, ops)
