"""Find a cell's files, and its engine, by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); its run parameters (chunk length,
warm-up, the replayed prefix) sit in ``bench/cells/<cell>.json``, and every
metric has a reader ``bench/metrics/<metric>.py``.  The configuration's
``engine`` key names the module ``bench/harness/engines/<engine>.py`` that
builds, runs and reads the program's state and holds its plain reference.
Nothing here names a cell or an engine, so a new cell, configuration, mix,
metric or engine is new files and entries.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# Top-level sections of a configuration or traffic file that feed SimConfig,
# its StoreProfile and its WorkloadSpec.
SECTIONS = ("sim", "store", "workload")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    engine: object                # the module bench/harness/engines/<engine>.py
    chips: int
    chunk_ticks: int
    warm_ticks: int
    ref_chunks: tuple[int, int]   # [lo, hi): replay up to a chunk drawn from the seed
    spec: dict                    # merged sim/store/workload fields
    end_to_end: tuple[dict, ...]  # BENCHMARK.json entries this cell reports
    per_layer: tuple[dict, ...]


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    return json.loads(path.read_text())


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    w = entries[name]
    conf = _read_json(BENCH / "configs" / f"{w['config']}.json")
    traffic = _read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    run = _read_json(BENCH / "cells" / f"{name}.json")
    spec = {s: {**conf.get(s, {}), **traffic.get(s, {})} for s in SECTIONS}
    chunk, warm = int(run["chunk_ticks"]), int(run["warm_ticks"])
    lo, hi = (int(x) for x in run["ref_chunks"])
    if chunk < 1 or warm < chunk or warm % chunk:
        raise ValueError(f"{name}: warm_ticks ({warm}) must be a positive "
                         f"multiple of chunk_ticks ({chunk})")
    if not warm // chunk < lo < hi:
        raise ValueError(f"{name}: ref_chunks [{lo}, {hi}) must lie past the "
                         f"{warm // chunk} warm-up chunks")
    return Cell(name=name, config=w["config"], traffic=w["traffic"],
                engine=engine_module(conf["engine"]), chips=int(w["chips"]),
                chunk_ticks=chunk, warm_ticks=warm, ref_chunks=(lo, hi), spec=spec,
                end_to_end=tuple(bench["end_to_end"]),
                per_layer=tuple(bench["per_layer"]))


def spec_with(cell: Cell, **sim_overrides) -> dict:
    """The cell's merged fields with some ``sim`` fields replaced."""
    return {**cell.spec, "sim": {**cell.spec["sim"], **sim_overrides}}


def sim_config(cell: Cell, **sim_overrides):
    """The program's ``SimConfig`` for a cell (seed 0: the seed enters the
    run through the initial PRNG key only, so every seed shares one compiled
    chunk).  ``sim_overrides`` lets tests shrink a cell."""
    from repro.core import backing_store as bs
    from repro.core import workload as wl
    from repro.core.simulator import SimConfig

    sim = dict(spec_with(cell, **sim_overrides)["sim"])
    for key in sim:
        if key not in SimConfig.__dataclass_fields__ or key in ("store", "workload", "seed"):
            raise KeyError(f"{cell.name}: unknown SimConfig field {key!r}")
    return SimConfig(
        **sim,
        store=bs.StoreProfile(**cell.spec["store"]),
        workload=wl.WorkloadSpec(**cell.spec["workload"]),
        seed=0,
    )


def _load(prefix: str, path: Path):
    spec = importlib.util.spec_from_file_location(f"{prefix}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """The ``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    return _load("bench_metric", path).read


def engine_module(name: str):
    """The module ``bench/harness/engines/<name>.py``, loaded once per path,
    so every cell of an engine shares it (and a fault planted in it)."""
    path = BENCH / "harness" / "engines" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"engine {name!r} has no module at {path}")
    return _engine_at(path)


@functools.cache
def _engine_at(path: Path):
    return _load("bench_engine", path)
