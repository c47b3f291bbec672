"""The engine-agnostic conformance contract for the FLIC tick semantics.

Three engines implement ONE tick semantics (DESIGN.md §8):

* ``reference`` — the retained pre-fusion per-pass pipeline
  (``core/simulator_ref.py``);
* ``fused``     — the batched hot path (``core/simulator.py``);
* ``distributed`` — the ``shard_map`` runtime (``core/distributed.py``),
  run on a 1-D mesh over every visible device (force 8 host devices with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

This module is the single source of truth for WHAT must match: the case
matrix (every ``workload.SCENARIOS`` preset, §VI outage schedules, loss-model
and insert-policy variants) and the bit-identity assertion — the full
``TickMetrics`` SERIES, and therefore the summarized metrics, must be equal
bitwise, not approximately (``metrics.diff_summaries``).  Per-case semantic
floors (``expect_positive``) guarantee the exercised paths are live, not
vacuously equal: ring forwarding under outages, cold churn rejoins, live
coherence sweeps, write coalescing.

Used three ways:

* imported by the pytest matrix (``tests/test_conformance.py`` drives it in
  an 8-device subprocess via the ``forced_devices_run`` fixture);
* imported by single-host tests (``tests/test_sim_equivalence.py`` reuses
  ``assert_series_identical``);
* run directly — ``python -m conformance [--cases a,b] [--seeds 0,1]
  [--engines reference,fused,distributed]`` prints a JSON report and exits
  nonzero on any divergence (the CI distributed job invokes exactly this).

Adding a new engine = one branch in ``simulator.run_any_engine`` returning
the standard ``(final_state, TickMetrics series)`` pair, plus its name in
``ENGINES`` here.  Nothing else: the cases and assertions are engine-blind.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

from repro.core.metrics import (
    EMBODIMENT_FIELDS,
    EMBODIMENT_SUMMARY_KEYS,
    diff_summaries,
    summarize,
)
from repro.core.simulator import SimConfig, run_any_engine
from repro.core.workload import SCENARIOS, WorkloadSpec

ENGINES = ("reference", "fused", "distributed")
SEEDS = (0, 1)

# Divides every forced host-device count in {1, 2, 4, 8}.
N_NODES = 16


@dataclasses.dataclass(frozen=True)
class ConformanceCase:
    cfg: SimConfig
    ticks: int
    # ``summarize`` fields that must be strictly positive on every seed —
    # proof the exercised semantics are live, not vacuously identical.
    expect_positive: tuple[str, ...] = ("reads",)
    # Metrics-thinning window (``run_any_engine(..., metrics_every=k)``) —
    # every engine, including distributed, must aggregate the same windows.
    metrics_every: int = 1


def _case(spec: WorkloadSpec, ticks: int, expect: tuple[str, ...] = (), **cfg_kw):
    metrics_every = cfg_kw.pop("metrics_every", 1)
    cfg = SimConfig(
        n_nodes=N_NODES, cache_lines=cfg_kw.pop("cache_lines", 64),
        loss_prob=cfg_kw.pop("loss_prob", 0.02), workload=spec, **cfg_kw,
    )
    return ConformanceCase(cfg, ticks, ("reads",) + expect, metrics_every)


_MUT = ("coherence_updates", "writes_coalesced")

CASES: dict[str, ConformanceCase] = {
    # -- every workload.SCENARIOS preset ------------------------------------
    "paper": _case(SCENARIOS["paper"], 90),
    "zipf": _case(SCENARIOS["zipf"], 100, _MUT),
    "zipf_hot": _case(SCENARIOS["zipf_hot"], 100, _MUT),
    "bursty": _case(SCENARIOS["bursty"], 130, _MUT),
    "diurnal": _case(SCENARIOS["diurnal"], 150, _MUT),
    "churn": _case(SCENARIOS["churn"], 150, _MUT + ("churn_rejoins",)),
    "storm": _case(SCENARIOS["storm"], 130, _MUT + ("churn_rejoins",)),
    # -- §VI outage schedules (deterministic, shared by all engines) --------
    "paper_outage": _case(
        SCENARIOS["paper"], 90, ("hit_queue_ratio",),
        outage_schedule=((25, 30),),
    ),
    "zipf_outage": _case(
        WorkloadSpec(popularity="zipf", key_universe=4096, zipf_alpha=0.9),
        110, _MUT + ("hit_queue_ratio",),
        read_period=5, loss_prob=0.05, cache_lines=32,
        outage_schedule=((30, 40),),
    ),
    # Outage overlapping a churn epoch boundary: nodes rejoin COLD while the
    # store is down, so their reads can only be served by fog peers or
    # writer-ring forwarding (the §VI path the matrix must keep live).
    "churn_outage": _case(
        WorkloadSpec(popularity="zipf", key_universe=4096, zipf_alpha=0.9,
                     churn_period=40, churn_fraction=0.3),
        110, _MUT + ("churn_rejoins", "hit_queue_ratio"),
        read_period=5, loss_prob=0.05, cache_lines=32,
        outage_schedule=((35, 40),),
    ),
    # -- metrics thinning: one aggregated row per 5-tick window, all three
    # engines (the distributed scan folds the same windows per shard) ------
    "zipf_thinned": _case(
        SCENARIOS["zipf"], 100, _MUT, metrics_every=5,
    ),
    # -- plan-stage workload axes (DESIGN.md §7): Poisson padded write
    # lanes, (T, N) trace replay, and the stream × churn combination that
    # needs the cumulative-write ring index --------------------------------
    "poisson": _case(SCENARIOS["poisson"], 100, _MUT),
    "trace": _case(SCENARIOS["trace_ycsb"], 120, _MUT),
    "stream_churn": _case(SCENARIOS["stream_churn"], 130, ("churn_rejoins",)),
    # -- fan-out-bounded gossip (DESIGN.md §9): the fused K-lane probe vs the
    # reference/distributed dense expansion of the same compact draws, with
    # response loss restricted to the ring neighborhood ---------------------
    "fanout_topk": _case(
        WorkloadSpec(popularity="zipf", key_universe=2048, zipf_alpha=0.9,
                     fanout=5),
        110, _MUT,
    ),
    # -- loss-model / insert-policy variants --------------------------------
    "paper_ge": _case(
        SCENARIOS["paper"], 70, loss_model="gilbert_elliott",
    ),
    "paper_replicate": _case(
        SCENARIOS["paper"], 60,
        insert_policy="replicate", loss_prob=0.1, cache_lines=32,
    ),
}


def assert_series_identical(a, b, label: str = ""):
    """Every ``TickMetrics`` field must match bit-for-bit over the series.

    ``metrics.EMBODIMENT_FIELDS`` (e.g. ``wire_bytes``) are excluded: they
    measure the mesh/collective embodiment, not the protocol, so they
    legitimately differ across engines and device counts.
    """
    for f in a.__dataclass_fields__:
        if f in EMBODIMENT_FIELDS:
            continue
        xa, xb = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        np.testing.assert_array_equal(
            xa, xb, err_msg=f"{label}: TickMetrics.{f} diverged"
        )


def run_case(name: str, seed: int, engine: str):
    """Run one case on one engine; returns (final_state, TickMetrics series)."""
    case = CASES[name]
    return run_any_engine(
        case.cfg, case.ticks, seed=seed, engine=engine,
        metrics_every=case.metrics_every,
    )


def case_report(name: str, seed: int, engines=ENGINES) -> dict:
    """Run one case on every engine and enforce the contract.

    Returns ``{engine: summary}``; raises AssertionError naming the first
    diverging field if any engine's series or summary differs from the
    first engine's, or if a semantic floor (``expect_positive``) is not met.
    """
    case = CASES[name]
    series_by, summary_by = {}, {}
    for engine in engines:
        _, series = run_case(name, seed, engine)
        series_by[engine] = series
        summary = summarize(series)
        for k in EMBODIMENT_SUMMARY_KEYS:  # embodiment-dependent, not compared
            summary.pop(k, None)
        summary_by[engine] = summary
    base = engines[0]
    for engine in engines[1:]:
        assert_series_identical(
            series_by[base], series_by[engine],
            f"{name}/seed{seed}: {base} vs {engine}",
        )
        d = diff_summaries(summary_by[base], summary_by[engine])
        assert not d, f"{name}/seed{seed}: {base} vs {engine} summary diff {d}"
    for field in case.expect_positive:
        assert summary_by[base][field] > 0, (
            f"{name}/seed{seed}: expected {field} > 0, got "
            f"{summary_by[base][field]} — the exercised path is not live"
        )
    return summary_by


# ---------------------------------------------------------------------------
# Tolerance tier: engine #4 (``sharded``, ``core/sharded.py``) trades
# bit-identity for traffic (DESIGN.md §10) — per-shard PRNG streams,
# shard-local gossip, consistent-hash home routing.  Its contract is a
# TOLERANCE column, not a bitwise one:
#
# * EXACT where the plan is deterministic: ``reads``, ``writes_gen`` and
#   ``churn_rejoins`` are PRNG-free functions of (t, node id), so the
#   sharded engine must reproduce them bit-for-bit;
# * EXACT durability conservation from the summaries alone:
#   ``writes_gen == writes_drained + final_queue_depth + queue_dropped +
#   writes_coalesced`` (the per-shard keyed rings partition the keyspace,
#   so the global ring invariant survives the psum);
# * WITHIN EPSILON for the loss-coupled ratios (miss rate, staleness):
#   different PRNG streams sample the same distributions;
# * LIVENESS floors, including ``wire_bytes_per_tick > 0`` on a real
#   multi-shard mesh (the engine must actually communicate).


@dataclasses.dataclass(frozen=True)
class ShardedTolerance:
    # |sharded - fused| bounds on summary ratios (tuned empirically; see
    # DESIGN.md §10 for the measured deltas these envelope).
    miss_ratio_eps: float
    stale_ratio_eps: float
    expect_positive: tuple[str, ...] = ()


# Epsilons envelope the measured 8-shard deltas at ~2x headroom (the cases
# issue only ~126 reads each, so the deltas are dominated by small-sample
# PRNG noise; measured maxima over seeds {0, 1}: zipf 0.152/0.004,
# zipf_hot 0.048/0.031, churn 0.093/0.001, zipf_outage 0.037/0.010).
SHARDED_CASES: dict[str, ShardedTolerance] = {
    "zipf": ShardedTolerance(0.25, 0.10, _MUT),
    "zipf_hot": ShardedTolerance(0.12, 0.10, _MUT),
    "churn": ShardedTolerance(0.18, 0.10, _MUT + ("churn_rejoins",)),
    "zipf_outage": ShardedTolerance(0.12, 0.10, _MUT),
}


def sharded_case_report(name: str, seed: int) -> dict:
    """Run one tolerance-tier case: ``sharded`` vs the bit-exact ``fused``.

    Raises AssertionError on any violated bound; returns
    ``{"sharded": summary, "fused": summary}``.
    """
    _, s_series = run_case(name, seed, "sharded")
    _, f_series = run_case(name, seed, "fused")
    ss, fs = summarize(s_series), summarize(f_series)
    assert_sharded_tolerance(
        SHARDED_CASES[name], ss, fs, f"sharded:{name}/seed{seed}"
    )
    return {"sharded": ss, "fused": fs}


def assert_sharded_tolerance(tol: ShardedTolerance, ss: dict, fs: dict,
                             label: str) -> None:
    """The tolerance-tier contract between a ``sharded`` summary ``ss`` and
    the bit-exact ``fused`` summary ``fs`` of the same config and seed."""
    import jax

    # Deterministic plan quantities are exact.
    for field in ("ticks", "reads", "writes_gen", "churn_rejoins"):
        assert ss[field] == fs[field], (
            f"{label}: {field} must be exact (deterministic plan): "
            f"sharded={ss[field]} fused={fs[field]}"
        )
    # Durability conservation, global over the per-shard keyed rings.
    budget = (ss["writes_drained"] + ss["final_queue_depth"]
              + ss["queue_dropped"] + ss["writes_coalesced"])
    assert ss["writes_gen"] == budget, (
        f"{label}: write conservation broken: gen={ss['writes_gen']} "
        f"!= drained+pending+dropped+coalesced={budget}"
    )
    # Loss-coupled ratios within the documented epsilons.
    d_miss = abs(ss["read_miss_ratio"] - fs["read_miss_ratio"])
    assert d_miss <= tol.miss_ratio_eps, (
        f"{label}: miss-ratio delta {d_miss:.4f} > eps {tol.miss_ratio_eps} "
        f"(sharded={ss['read_miss_ratio']:.4f} fused={fs['read_miss_ratio']:.4f})"
    )
    d_stale = abs(ss["stale_read_ratio"] - fs["stale_read_ratio"])
    assert d_stale <= tol.stale_ratio_eps, (
        f"{label}: stale-ratio delta {d_stale:.4f} > eps {tol.stale_ratio_eps}"
    )
    # Liveness floors.
    for field in ("reads",) + tol.expect_positive:
        assert ss[field] > 0, (
            f"{label}: expected {field} > 0, got {ss[field]}"
        )
    if jax.device_count() > 1:
        assert ss["wire_bytes_per_tick"] > 0, (
            f"{label}: multi-shard run reported zero on-wire bytes"
        )


def main(argv=None) -> None:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--cases", default=None,
                   help="comma-separated case names (default: all)")
    p.add_argument("--seeds", default=",".join(str(s) for s in SEEDS))
    p.add_argument("--engines", default=",".join(ENGINES))
    p.add_argument("--sharded", default="all",
                   help="tolerance-tier cases for the sharded engine: "
                        "'all' (default), 'none', or comma-separated names")
    a = p.parse_args(argv)
    names = a.cases.split(",") if a.cases else list(CASES)
    seeds = [int(s) for s in a.seeds.split(",")]
    engines = tuple(a.engines.split(","))
    report: dict = {}
    for name in names:
        for seed in seeds:
            report.setdefault(name, {})[str(seed)] = case_report(
                name, seed, engines
            )
    if a.sharded != "none":
        sharded_names = (
            list(SHARDED_CASES) if a.sharded == "all"
            else a.sharded.split(",")
        )
        tier: dict = {}
        for name in sharded_names:
            for seed in seeds:
                tier.setdefault(name, {})[str(seed)] = sharded_case_report(
                    name, seed
                )
        report["__sharded_tolerance__"] = tier
    print(json.dumps(report))


if __name__ == "__main__":
    main()
