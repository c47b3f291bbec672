#!/usr/bin/env python3
"""Readings that set the correctness limits of a cell, at its own size.

For each seed it prints one JSON line with the numbers ``check.verify``
gives the program (its chunk calls from tick 0 past the seed's snapshot
chunk, as a run makes them) and the numbers it gives the control (the
reference in bfloat16 put in the program's place); then a summary: the
largest program reading and the smallest control reading of each number.  The limits in
``harness/check.py`` lie between the two.  With ``--fault`` the program
runs with that fault planted (``harness/faults.py``) and has to read not
correct on every seed.

Usage, on the chip::

    python3 bench/control.py --workload ycsb_a_n10k --seeds 1,2,3
    python3 bench/control.py --workload paper_stream_n20k --seeds 4,5,6 \
        --fault wrong_victim

The benchmark's own runs do not run this.  Without a TPU it exits 2.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]
EXTRA_CHUNKS = 8   # chunks run past the snapshot chunk, as a run runs on


def main(argv=None) -> int:
    from harness import faults

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated seeds")
    p.add_argument("--fault", choices=faults.FAULTS, default=None,
                   help="plant this fault in the program; no control")
    args = p.parse_args(argv)

    from run_cell import chips_missing, keep_logs_in_tmpdir

    keep_logs_in_tmpdir()
    import jax
    from harness import cells, check, control, driver

    cell = cells.load_cell(args.workload)
    missing = chips_missing(jax.devices(), cell.chips)
    if missing:
        print(f"control: {missing}", file=sys.stderr)
        return 2
    driver.use_compile_cache(ROOT)
    cfg = cells.sim_config(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    lower, upper, passed = {}, {}, 0
    fault = (faults.planted(args.fault, cell.engine) if args.fault
             else contextlib.nullcontext())
    with fault:
        for seed in seeds:
            prog, snap, info = control.program_numbers(
                cfg, cell.spec, cell, seed, EXTRA_CHUNKS)
            passed += check.correct(prog)
            line = {"seed": seed, "snapshot_chunk": snap, "replay": info,
                    "program": prog, "correct": check.correct(prog)}
            if not args.fault:
                line["control"] = control.control_numbers(
                    cell, cell.spec, seed, snap)
                for k, v in line["control"].items():
                    upper[k] = min(upper.get(k, v), v)
            print(json.dumps(line), flush=True)
            for k, v in prog.items():
                lower[k] = max(lower.get(k, v), v)
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "program_correct": f"{passed}/{len(seeds)}",
                      "lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
