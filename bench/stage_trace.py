#!/usr/bin/env python3
"""Trace chunks of a cell and put their device time and idle gaps down to the
tick's stages and the runtime's host work.

Usage, on the chip::

    python3 bench/stage_trace.py --workload ycsb_a_n10k --seed 7 --seconds 51
    python3 bench/stage_trace.py --workload ycsb_a_n10k --seed 7 --chunks 3 \
        --n-nodes 500 --save bench/tests/data/ycsb_a_n500_3chunks

Set-up is a run's (``harness.driver``): the state built from the seed, the
compile cache, the warm-up chunks.  ``--seconds`` then traces a run's own
window (``driver.drive``: chunks for that long and on to the snapshot
chunk, whose state is fetched inside the window); ``--chunks`` traces just
that many chunks, for a trace small enough to keep.  Either way the chunks
run under JAX's profiler inside the benchmark's ``dispatch`` and ``fetch``
spans; the chunk program's text comes from the cell's engine's compile of
it after the window (a compile-cache load), and ``harness.stages`` reduces
the trace with it.  One JSON line gives the metrics of ``bench/metrics/``
that read the trace, ``breakdown.stages``, the idle gaps labelled
``<span>/<event>`` and the seconds each step of the reduction took.
``--save PREFIX`` keeps the trace as ``PREFIX.xplane.pb`` and the program's
text as ``PREFIX.hlo.txt.gz``.

``run_cell.py`` does not run this.  Without a TPU it exits 2.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    p.add_argument("--seed", type=int, required=True)
    length = p.add_mutually_exclusive_group(required=True)
    length.add_argument("--seconds", type=float,
                        help="trace a run's window of this length")
    length.add_argument("--chunks", type=int,
                        help="trace this many chunks after the warm-up")
    p.add_argument("--n-nodes", type=int, default=None,
                   help="run the cell at this N instead of its own")
    p.add_argument("--save", default=None,
                   help="keep the trace and the program's text under this prefix")
    return p.parse_args(argv)


def traced_window(cell, cfg, args):
    """Warm up as a run does, then trace the window; returns (the final
    state, the tracer)."""
    import jax
    from harness import driver
    from harness.trace import Tracer

    tracer = Tracer()
    if args.seconds is not None:
        record = driver.RunRecord(cell=cell, seed=args.seed, seconds=args.seconds,
                                  t_start=time.perf_counter())
        state, _ = driver.drive(record, cfg, driver.CompileCounter(),
                                jax.devices(), tracer)
        return state, tracer
    chunk = cell.engine.chunk_fn(cfg, cell.chunk_ticks)
    state = driver.build(cell, cfg, args.seed, jax.devices())
    for _ in range(cell.warm_ticks // cell.chunk_ticks):
        state, row = chunk(state)
        jax.device_get(row)
    tracer.start()
    for _ in range(args.chunks):
        with driver._annotate(tracer, "dispatch"):
            state, row = chunk(state)
        with driver._annotate(tracer, "fetch"):
            jax.device_get(row)
    tracer.stop()
    return state, tracer


def main(argv=None) -> int:
    args = parse(argv)
    from run_cell import chips_missing, keep_logs_in_tmpdir

    keep_logs_in_tmpdir()
    import jax
    from harness import cells, driver, stages, trace

    cell = cells.load_cell(args.workload)
    missing = chips_missing(jax.devices(), cell.chips)
    if missing:
        print(f"stage_trace: {missing}", file=sys.stderr)
        return 2
    driver.use_compile_cache(ROOT)
    # The cache's key leaves out the source metadata unless told otherwise,
    # so a program compiled without the stage scopes (another checkout
    # sharing the cache) would serve this one, and its text would name no
    # stage.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cfg = cells.sim_config(cell, **({"n_nodes": args.n_nodes} if args.n_nodes else {}))
    state, tracer = traced_window(cell, cfg, args)

    secs = {}
    t0 = time.perf_counter()
    compiled = cell.engine.lower(cfg, cell.chunk_ticks, state)
    t1 = time.perf_counter()
    text = compiled.as_text()
    secs["compile"], secs["as_text"] = t1 - t0, time.perf_counter() - t1
    try:
        path = tracer.xspace_path()
        t0 = time.perf_counter()
        devices, spans, runtime = stages.events(path)
        t1 = time.perf_counter()
        summary = trace.reduce_events(devices, spans)
        t2 = time.perf_counter()
        ops = stages.op_stages(text)
        stage_summary = stages.reduce(devices, spans, runtime, ops)
        t3 = time.perf_counter()
        secs.update(events=t1 - t0, reduce_events=t2 - t1, stages=t3 - t2)
        if args.save:
            Path(args.save).parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, f"{args.save}.xplane.pb")
            with gzip.open(f"{args.save}.hlo.txt.gz", "wt") as f:
                f.write(text)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)

    record = driver.RunRecord(cell=cell, seed=args.seed, seconds=0.0, t_start=0.0)
    record.trace_summary = summary
    record.stage_summary = stage_summary
    units = {"device.busy_ms_per_tick": "ms", "device.idle_share": "%",
             **{f"stage.{s}_ms_per_tick": "ms" for s in stages.STAGES},
             "host.launch_ms": "ms", "host.fetch_transfers_per_chunk": "transfers/chunk"}
    metrics = {}
    for name, unit in units.items():
        value = cells.metric_reader(name)(record)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    scoped = sum(1 for stage, _ in ops.values() if stage != "unscoped")
    line = {"workload": cell.name, "n_nodes": cfg.n_nodes, "seed": args.seed,
            "device": {"kind": jax.devices()[0].device_kind,
                       "busy_s": summary.busy_s, "window_s": summary.window_s},
            "chunks": summary.chunks, "metrics": metrics,
            "breakdown": {"stages": stages.breakdown(stage_summary, cell.chunk_ticks),
                          "idle_gaps": stage_summary.idle_gaps,
                          "device_ops": summary.top_ops},
            "program": {"instructions": len(ops), "scoped": scoped,
                        "text_bytes": len(text)},
            "reduction_s": secs}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
