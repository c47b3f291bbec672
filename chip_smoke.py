#!/usr/bin/env python3
"""Bring-up smoke of the FLIC fog simulator on a TPU.

Drives the main path, ``run_any_engine``, on one chip and checks what comes
out:

a. city scale: the fused engine with the inline probe on ``paper`` and
   ``zipf_hot``, fan-out K=32, N=10^4 nodes, 200-line caches, 8-lane
   payloads, 600 ticks.  Reads follow the ``read_period`` schedule exactly,
   ``paper`` generates N writes per tick, generated writes are conserved
   (drained + pending + dropped + coalesced), and the coherence sweep is
   live on ``zipf_hot`` and idle on ``paper``;
b. the plain reference engine (``core/simulator_ref.py``) against fused on
   both scenarios at N=2000, K=32: bit-identical ``TickMetrics`` series;
c. the configs of b through the Pallas kernels (``probe_backend="pallas"``):
   bit-identical to b, with ``tpu_custom_call`` in the compiled scan, so an
   interpreted kernel cannot pass unseen.

``--four-chips`` runs phase d alone, on a 1-D mesh over four chips:

d. ``engine="distributed"`` bit-identical to fused on one of the devices
   (metric series and final caches),
   ``engine="sharded"`` on ``zipf_hot`` at N=10^4, K=32 within the
   ``SHARDED_CASES`` tolerance tier of ``tests/conformance.py``, and the
   cache state split over all four devices.

Each run prints one JSON line: its config, the backend compile seconds of
the first call, a steady ticks/s from a second call (a smoke timing, not a
benchmark; phases a-c only), the device's peak memory so far and
``summarize`` of the series.  The last line, ``{"ok": true, "device": {...}}``, is printed only
when every phase passed.  Without a TPU the script exits nonzero.

Usage: ``python chip_smoke.py [--four-chips] [--seed S]``
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

SCENARIO_NAMES = ("paper", "zipf_hot")
FANOUT = 32
CITY_NODES, CITY_TICKS = 10_000, 600     # phase a (and the sharded run of d)
REF_NODES, REF_TICKS = 2_000, 60         # phases b, c (and the parity run of d)
FOUR_TICKS = 60

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile = {"secs": 0.0, "count": 0}


def _on_duration(event: str, secs: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _compile["secs"] += secs
        _compile["count"] += 1


def city_config(scenario: str, n_nodes: int, probe_backend=None):
    from repro.core import SCENARIOS, SimConfig

    spec = dataclasses.replace(SCENARIOS[scenario], fanout=FANOUT)
    return SimConfig(n_nodes=n_nodes, cache_lines=200, payload_dim=8,
                     workload=spec, probe_backend=probe_backend)


def describe(cfg, scenario: str, ticks: int, engine: str) -> dict:
    return dict(scenario=scenario, engine=engine, n_nodes=cfg.n_nodes,
                fanout=cfg.workload.fanout, cache_lines=cfg.cache_lines,
                cache_ways=cfg.cache_ways, payload_dim=cfg.payload_dim,
                probe_backend=cfg.probe_backend, ticks=ticks)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed_run(phase: str, cfg, scenario: str, ticks: int, engine: str,
              seed: int, steady: bool = True):
    """Run one engine; with ``steady`` run it again and time the second call.

    The first call compiles; the second must give the same series and
    compile nothing.  Prints the run's JSON line and returns
    (final_state, series, summary).
    """
    from conformance import assert_series_identical
    from repro.core import summarize
    from repro.core.simulator import run_any_engine

    def call():
        return jax.block_until_ready(
            run_any_engine(cfg, ticks, seed=seed, engine=engine))

    secs0, n0 = _compile["secs"], _compile["count"]
    t0 = time.perf_counter()
    final, series = call()
    first_s = time.perf_counter() - t0
    compile_s, compiles = _compile["secs"] - secs0, _compile["count"] - n0
    steady_s = None
    if steady:
        n1 = _compile["count"]
        t0 = time.perf_counter()
        final, again = call()
        steady_s = time.perf_counter() - t0
        assert _compile["count"] == n1, f"{phase}/{scenario}/{engine}: rerun compiled"
        assert_series_identical(series, again, f"{phase}/{scenario}/{engine} rerun")
    summary = summarize(series)
    print(json.dumps(dict(
        phase=phase, config=describe(cfg, scenario, ticks, engine), seed=seed,
        compile_s=compile_s, compiles=compiles, first_call_s=first_s,
        steady_call_s=steady_s,
        smoke_ticks_per_s=ticks / steady_s if steady else None,
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
        summary=summary,
    )), flush=True)
    return final, series, summary


def expected_reads(n_nodes: int, ticks: int, read_period: int) -> np.ndarray:
    """Readers per tick: nodes n with (t + n) % read_period == 0, from t=1."""
    t = np.arange(ticks)[:, None]
    node = np.arange(n_nodes)[None, :]
    return (((t + node) % read_period == 0) & (t > 0)).sum(axis=1)


def assert_conserved(s: dict, label: str) -> None:
    budget = (s["writes_drained"] + s["final_queue_depth"]
              + s["queue_dropped"] + s["writes_coalesced"])
    assert s["writes_gen"] == budget, (
        f"{label}: generated {s['writes_gen']} != drained + pending + "
        f"dropped + coalesced {budget}")


def phase_city(seed: int, n_nodes: int = CITY_NODES,
               ticks: int = CITY_TICKS) -> None:
    for scenario in SCENARIO_NAMES:
        cfg = city_config(scenario, n_nodes)
        _, series, s = timed_run("a", cfg, scenario, ticks, "fused", seed)
        label = f"a/{scenario}"
        np.testing.assert_array_equal(
            np.asarray(series.reads),
            expected_reads(n_nodes, ticks, cfg.read_period),
            err_msg=f"{label}: reads do not follow the read_period schedule")
        if scenario == "paper":
            assert s["writes_gen"] == n_nodes * ticks, (label, s["writes_gen"])
            assert s["coherence_updates"] == 0, (label, s["coherence_updates"])
        else:
            assert s["coherence_updates"] > 0, (label, s["coherence_updates"])
        assert_conserved(s, label)


def phase_reference(seed: int, n_nodes: int = REF_NODES,
                    ticks: int = REF_TICKS) -> dict:
    """Returns the fused series per scenario, for phase c."""
    from conformance import assert_series_identical

    fused = {}
    for scenario in SCENARIO_NAMES:
        cfg = city_config(scenario, n_nodes)
        _, ref, _ = timed_run("b", cfg, scenario, ticks, "reference", seed)
        _, fused[scenario], _ = timed_run("b", cfg, scenario, ticks, "fused",
                                          seed)
        assert_series_identical(ref, fused[scenario], f"b/{scenario}")
    return fused


def assert_kernels_lowered(cfg, ticks: int, label: str) -> None:
    """The compiled scan must call Mosaic kernels, not interpret them."""
    from repro.core.simulator import _run_scan, init_sim

    hlo = _run_scan.lower(cfg, ticks, init_sim(cfg), 1, "fused").compile().as_text()
    assert "tpu_custom_call" in hlo, (
        f"{label}: no tpu_custom_call in the compiled scan — the Pallas "
        "kernels did not lower for the chip")


def phase_kernels(seed: int, fused: dict, n_nodes: int = REF_NODES,
                  ticks: int = REF_TICKS) -> None:
    from conformance import assert_series_identical

    for scenario in SCENARIO_NAMES:
        cfg = city_config(scenario, n_nodes, probe_backend="pallas")
        _, series, _ = timed_run("c", cfg, scenario, ticks, "fused", seed)
        assert_kernels_lowered(cfg, ticks, f"c/{scenario}")
        assert_series_identical(fused[scenario], series, f"c/{scenario}")


def phase_four_chips(seed: int, parity_nodes: int = REF_NODES,
                     sharded_nodes: int = CITY_NODES,
                     ticks: int = FOUR_TICKS) -> None:
    """One call per run: the mesh engines trace a new scan on every call,
    so a second call would compile again rather than time a steady run."""
    from conformance import (SHARDED_CASES, assert_series_identical,
                             assert_sharded_tolerance)

    devices = jax.devices()
    for scenario in SCENARIO_NAMES:
        cfg = city_config(scenario, parity_nodes)
        par_final, par, _ = timed_run("d", cfg, scenario, ticks, "distributed",
                                      seed, steady=False)
        with jax.default_device(devices[0]):
            one_final, one, _ = timed_run("d", cfg, scenario, ticks, "fused",
                                          seed, steady=False)
        label = f"d/{scenario}: distributed"
        assert_series_identical(one, par, label)
        for field in one_final.caches.__dataclass_fields__:
            np.testing.assert_array_equal(
                np.asarray(getattr(par_final.caches, field)),
                np.asarray(getattr(one_final.caches, field)),
                err_msg=f"{label}: final caches.{field}")

    cfg = city_config("zipf_hot", sharded_nodes)
    final, _, ss = timed_run("d", cfg, "zipf_hot", ticks, "sharded",
                             seed, steady=False)
    assert_cache_split(final.caches, devices)
    with jax.default_device(devices[0]):
        _, _, fs = timed_run("d", cfg, "zipf_hot", ticks, "fused",
                             seed, steady=False)
    assert_sharded_tolerance(SHARDED_CASES["zipf_hot"], ss, fs,
                             "d/zipf_hot: sharded")


def assert_cache_split(caches, devices) -> None:
    """The sharded cache state sits in equal parts on every device."""
    from repro.utils.trees import tree_bytes

    cache_bytes = tree_bytes(caches)
    held = {}
    for leaf in jax.tree.leaves(caches):
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in devices}
    print(json.dumps(dict(phase="d", cache_state_bytes=cache_bytes,
                          cache_bytes_by_device={d.id: b for d, b in held.items()},
                          bytes_in_use_by_device=in_use)), flush=True)
    assert set(held) == set(devices), (
        f"cache state sits on {sorted(d.id for d in held)}, not on all "
        f"{len(devices)} devices")
    for d, b in held.items():
        assert b * len(devices) == cache_bytes, (d.id, b, cache_bytes)
        assert in_use[d.id] is None or in_use[d.id] >= b, (d.id, in_use[d.id], b)


def run_phases(phases) -> bool:
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            print(json.dumps({"phase": name, "failed": True}), flush=True)
            failed.append(name)
    return not failed


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only phase d, on four chips")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the simulated traffic and channel draws")
    args = p.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform} "
              f"devices); this smoke runs only on a TPU", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(json.dumps({"compile_cache_dir": cache_dir}), flush=True)

    if args.four_chips:
        phases = [("d", lambda: phase_four_chips(args.seed))]
    else:
        fused = {}
        phases = [
            ("a", lambda: phase_city(args.seed)),
            ("b", lambda: fused.update(phase_reference(args.seed))),
            ("c", lambda: phase_kernels(args.seed, fused)),
        ]
    if not run_phases(phases):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
