"""The engine layer: a configuration's ``engine`` names a module under
``bench/harness/engines/``, a missing one fails at once, a new engine is a
new file that the harness drives unchanged, and the invariants bound the
drain by the engine's writer rings."""
import copy
import json
import re
import shutil

import jax
import numpy as np
import pytest

import run_cell
from harness import cells, check, driver, reference

BENCH = cells.BENCH
CONFIGS = sorted((BENCH / "configs").glob("*.json"))
ENGINE_API = ("build", "chunk_fn", "lower", "state_leaves", "Reference",
              "writer_rings")

STUB = '''"""The program's retained pre-fusion tick, bit-identical to the fused one,
as an engine of its own: the fused engine's state and reference."""
from harness.engines import fused
from repro.core import simulator

Reference = fused.Reference
state_leaves = fused.state_leaves
writer_rings = fused.writer_rings
HANDED = []


def build(cfg, seed, devices):
    HANDED.append(list(devices))
    return fused.build(cfg, seed, devices)


def chunk_fn(cfg, chunk_ticks):
    def run(state):
        return simulator._run_scan(cfg, chunk_ticks, state, chunk_ticks,
                                   "reference")
    return run


def lower(cfg, chunk_ticks, state):
    return simulator._run_scan.lower(cfg, chunk_ticks, state, chunk_ticks,
                                     "reference").compile()
'''


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A checkout of the benchmark's files in ``tmp_path`` that ``cells``
    reads instead of the repository's; returns a function that sets the
    engine of a configuration there."""
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(BENCH / sub, tmp_path / "bench" / sub)
    (tmp_path / "bench" / "harness" / "engines").mkdir(parents=True)
    monkeypatch.setattr(cells, "ROOT", tmp_path)
    monkeypatch.setattr(cells, "BENCH", tmp_path / "bench")

    def set_engine(config: str, engine: str):
        path = tmp_path / "bench" / "configs" / f"{config}.json"
        conf = json.loads(path.read_text())
        conf["engine"] = engine
        path.write_text(json.dumps(conf))
        return tmp_path / "bench" / "harness" / "engines" / f"{engine}.py"

    return set_engine


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_every_configuration_names_an_engine_with_a_module(path):
    conf = json.loads(path.read_text())
    module = cells.engine_module(conf["engine"])
    for name in ENGINE_API:
        assert callable(getattr(module, name)), name
    spec = {s: conf.get(s, {}) for s in cells.SECTIONS}
    assert module.writer_rings(spec) >= 1


def test_unknown_engine_fails_in_load_cell_naming_the_path(bench_copy):
    missing = bench_copy("flic_ycsb_a_n10k", "no_such_engine")
    with pytest.raises(FileNotFoundError, match=re.escape(str(missing))):
        cells.load_cell("ycsb_a_n10k")


def test_a_new_engine_is_only_a_new_file(bench_copy):
    """A stub engine in another checkout is found by name, handed the
    cell's one chip of the devices given, and a shrunk run through it
    counts 0 on every exact number and keeps the float gap in its limit."""
    bench_copy("flic_ycsb_a_n10k", "stub").write_text(STUB)
    cell = cells.load_cell("ycsb_a_n10k")
    assert cell.engine.__file__ == str(cells.BENCH / "harness" / "engines"
                                       / "stub.py")
    devices = jax.devices() * 3
    args = run_cell.parse(["--workload", cell.name, "--seed", str(2**31 + 17),
                           "--seconds", "0.2", "--trace", "0"])
    line = run_cell.run(args, cell, devices, sim_overrides={"n_nodes": 48})
    assert cell.engine.HANDED == [devices[:cell.chips]]
    assert line["correct"] is True, line["checks"]
    counts = {k: c["value"] for k, c in line["checks"].items() if k != "float_gap"}
    assert counts == dict.fromkeys(counts, 0) and len(counts) == 4
    cfg = cells.sim_config(cell, n_nodes=48)
    state = driver.build(cell, cfg, 1, devices)
    assert driver.program_temp_bytes(cell, cfg, state) > 0


N, PERIOD, MAX_PER_TICK, CHUNK, RINGS = 1000, 1, 64, 2, 4


def _four_writer_run():
    """Three chunks of synthetic rows in which four writers each drain their
    full bound, and a final state whose ring and store leaves carry a
    leading axis of four rings."""
    spec = {"sim": {"n_nodes": N, "read_period": PERIOD,
                    "writer_max_per_tick": MAX_PER_TICK}}
    drained = RINGS * MAX_PER_TICK * CHUNK
    rows = []
    for i in range(3):
        reads = check.expected_reads(N, PERIOD, i * CHUNK, (i + 1) * CHUNK)
        row = dict.fromkeys(reference.INT_FIELDS, 0)
        row.update(ticks=CHUNK, reads=reads, hits_local=reads,
                   writes_gen=N * CHUNK, writes_drained=drained,
                   writes_coalesced=1400, queue_depth=88 * (i + 1))
        rows.append(row)
    final = {"tick": np.int32(3 * CHUNK),
             "queue.head": np.array([5, 0, 7, 1], np.int32),
             "queue.tail": np.array([71, 66, 73, 67], np.int32),
             "queue.dropped": np.zeros(RINGS, np.int32),
             "store.drained_total": np.array([384, 380, 388, 384], np.int32),
             "store.lost_writes": np.zeros(RINGS, np.int32)}
    return rows, final, spec


def test_invariants_accept_a_four_writer_drain():
    rows, final, spec = _four_writer_run()
    assert check.invariants(rows, final, spec, CHUNK, RINGS) == (0, set())
    # one writer could not have drained it: each chunk breaks its bound
    assert check.invariants(rows, final, spec, CHUNK, 1) == (3, {0, 1, 2})


def test_invariants_count_a_lost_write_over_four_rings():
    rows, final, spec = _four_writer_run()
    lost = copy.deepcopy(final)
    lost["store.drained_total"][2] -= 1
    assert check.invariants(rows, lost, spec, CHUNK, RINGS) == (1, {2})
    pending = copy.deepcopy(final)
    pending["queue.tail"][3] -= 1
    assert check.invariants(rows, pending, spec, CHUNK, RINGS) == (1, {2})
