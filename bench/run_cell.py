#!/usr/bin/env python3
"""Run one cell of the FLIC chip benchmark once and print its result line.

Usage (from the root of a checkout, on a machine with the cell's chips)::

    python3 bench/run_cell.py --workload paper_stream_n20k --seed 7 \
        --seconds 30 --trace 0

The cell's configuration, traffic mix and run parameters are found by the
names in ``BENCHMARK.json``, and the configuration's ``engine`` names the
module ``bench/harness/engines/<engine>.py`` that runs it.  Set-up has that
engine build the fog's state on the cell's chips from the seed and warms
its one chunk program; the window then runs chunks back to back for
``--seconds``, each one call of the engine's chunk program.  Afterwards the
engine's plain reference replays the run from tick 0 past the caches' fill
to a chunk drawn from the seed, and the whole run is checked (``correct``).
With ``--trace 1`` the window runs under JAX's profiler and the line carries
the per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.  The last lines on standard error, and the ``checks``
key that ends the result line, give every number compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]


def keep_logs_in_tmpdir() -> None:
    """libtpu logs to ``/tmp/tpu_logs`` unless told otherwise; a run writes
    only inside its checkout and its own ``TMPDIR``."""
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    p.add_argument("--seed", type=int, required=True,
                   help="seed of the fog's traffic and channel draws")
    p.add_argument("--seconds", type=float, required=True,
                   help="length of the measured window")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0,
                   help="1: trace the window and report the per-layer metrics")
    return p.parse_args(argv)


def chips_missing(devices, chips: int) -> str | None:
    if devices[0].platform != "tpu":
        return f"no TPU found (JAX sees {devices[0].platform} devices)"
    if len(devices) < chips:
        return f"the cell needs {chips} TPU chips, JAX sees {len(devices)}"
    return None


def chunk_profile(chunk_s: list) -> dict:
    """Chunk wall times in ms: quantiles, and the chunks over 1.5x the median
    (stalls), so a slow run's record shows what made it slow."""
    import numpy as np

    ms = np.asarray(chunk_s) * 1e3
    med = float(np.median(ms))
    slow = np.nonzero(ms > 1.5 * med)[0]
    return {"min": float(ms.min()), "p50": med,
            "p95": float(np.percentile(ms, 95)), "max": float(ms.max()),
            "over_1.5x_median": int(slow.size),
            "first_slow_chunks": [int(i) for i in slow[:10]]}


def run(args, cell, devices, sim_overrides=None, t_start: float = T_START) -> dict:
    """Set up, drive the window, check, and build the result line."""
    import jax
    from harness import check, cells, driver
    from harness.trace import Tracer

    cfg = cells.sim_config(cell, **(sim_overrides or {}))
    spec = cells.spec_with(cell, **(sim_overrides or {}))
    counter = driver.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    record = driver.RunRecord(cell=cell, seed=args.seed, seconds=args.seconds,
                              t_start=t_start)
    tracer = Tracer() if args.trace else None
    record.mark("chip_ready")
    final, snapshot = driver.drive(record, cfg, counter, devices, tracer)
    used = devices[:cell.chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    record.peak_bytes = max(p for p in peaks if p is not None) if any(
        p is not None for p in peaks) else None
    if tracer is not None:
        record.trace_summary = tracer.summary()
        record.temp_bytes = driver.program_temp_bytes(cell, cfg, final)

    t0 = time.perf_counter()
    rows = check.rows_to_host(record.rows)
    fin = cell.engine.state_leaves(final)
    del final
    t1 = time.perf_counter()
    numbers, failed, replay = check.verify(cell.engine, rows, snapshot, fin,
                                           record.snap_chunk, spec, args.seed,
                                           cell.chunk_ticks)
    replay["fetch_s"] = t1 - t0
    replay["check_s"] = time.perf_counter() - t1
    ok = check.correct(numbers)

    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cells.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": record.peak_bytes}
    line = {"correct": ok, "attempted": len(rows), "failed": len(failed),
            "metrics": metrics, "device": device}
    ts = record.trace_summary
    if ts is not None:
        device["busy_s"] = ts.busy_s
        device["window_s"] = ts.window_s
        line["breakdown"] = {"device_ops": ts.top_ops, "idle_gaps": ts.idle_gaps}
        line["busy_ms_per_tick_halves"] = [
            b * 1e3 / (n * cell.chunk_ticks) if n else None
            for b, n in zip(ts.busy_s_halves, ts.half_chunks)]
    line["window"] = {"chunks": len(record.chunk_s), "ticks": record.ticks,
                      "seconds": record.window_s,
                      "compiles_in_window": record.window_compiles,
                      "chunk_ms": chunk_profile(record.chunk_s),
                      "snapshot_chunk": record.snap_chunk,
                      "replay": replay,
                      "setup_marks_s": record.setup_marks}
    line["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                      for k, v in numbers.items()}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    keep_logs_in_tmpdir()
    from harness import cells

    cell = cells.load_cell(args.workload)
    import jax

    devices = jax.devices()
    missing = chips_missing(devices, cell.chips)
    if missing:
        print(f"run_cell: {missing}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    from harness.driver import use_compile_cache

    use_compile_cache(ROOT)
    line = run(args, cell, devices)
    if line["window"]["compiles_in_window"]:
        print(f"run_cell: {line['window']['compiles_in_window']} compile(s) "
              "inside the window", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
