"""Benchmark harness entry point.

Emits ``name,us_per_call,derived`` CSV — one section per paper table/figure
(Figs. 2-5 + abstract claims + §II-B bound), kernel microbenchmarks, and
the distributed two-engine sweep.

Everything runs in this one process over the visible devices, so on a TPU
host no child process competes for the chip.  A section that raises prints
a ``<section>.failed`` line with the reason, the later sections still run,
and the harness exits nonzero at the end.

Usage: ``PYTHONPATH=src python -m benchmarks.run [--quick]``.  On a CPU host,
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` lets the distributed
sweep reach 8 shards.
"""
from __future__ import annotations

import sys
import traceback

_FAILED: list[str] = []


def _section(name: str, fn, *args, **kwargs):
    """Run one bench section; a failure is printed and remembered."""
    try:
        return fn(*args, **kwargs)
    except Exception as e:  # noqa: BLE001 — reported, then exit nonzero
        traceback.print_exc()
        reason = f"{type(e).__name__}: {e}"
        print(f"{name}.failed,0.0,{reason.splitlines()[0][:160]!r}")
        _FAILED.append(name)
        return None


def main() -> None:
    quick = "--quick" in sys.argv
    scale = 0.25 if quick else 1.0

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import figs
    print("name,us_per_call,derived")
    _section("figs.headline", figs.headline, ticks=int(1200 * scale))
    _section("figs.fig2", figs.fig2_latency, ticks=int(400 * scale))
    _section("figs.fig3", figs.fig3_bandwidth, ticks=int(600 * scale))
    _section("figs.fig4", figs.fig4_miss_ratio, ticks=int(800 * scale))
    _section("figs.fig5", figs.fig5_txn_size, ticks=int(600 * scale))
    _section("figs.coherence_bound", figs.coherence_bound)

    from benchmarks.kernels_bench import bench_kernels
    _section("kernels", bench_kernels)

    from benchmarks.sim_bench import bench_sim
    _section(
        "sim", bench_sim,
        ticks=int(600 * scale),
        # quick mode skips N=500 and the fused-only N=1000 row: the
        # reference engine alone needs ~80 s at N=500
        node_counts=(50, 200) if quick else (50, 200, 500),
        fused_only_counts=() if quick else (1000,),
    )

    from benchmarks.scenario_bench import bench_scenarios
    _section(
        "scenarios", bench_scenarios,
        ticks=int(600 * scale),
        scenarios=("paper", "zipf", "churn") if quick else None,
        # quick mode skips the backend sweep (the interpret backend is the
        # Pallas interpreter — far too slow for a quick pass)
        backend_ticks=0 if quick else 150,
    )

    # Distributed two-engine 1/2/4/8-shard sweep -> BENCH_distributed.json,
    # over the devices this process sees (rows past that count are skipped).
    from benchmarks.distributed_bench import bench_distributed
    _section("distributed", bench_distributed, ticks=int(400 * scale))

    if _FAILED:
        sys.exit(f"failed sections: {', '.join(_FAILED)}")


if __name__ == "__main__":
    main()
