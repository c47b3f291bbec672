"""The window's chunks continue one simulation: the chunk calls of a cell's
engine from its initial state give the rows of one ``run_sim`` with
``metrics_every=chunk_ticks``, bit for bit, and the same final state."""
import jax
import numpy as np
import pytest

from harness import cells, driver

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]
N_SMALL = 64


@pytest.mark.parametrize("name", CELLS)
def test_chunked_scan_equals_one_run(name):
    from repro.core.simulator import run_sim

    cell = cells.load_cell(name)
    cfg = cells.sim_config(cell, n_nodes=N_SMALL)
    chunks, k = 5, max(cell.chunk_ticks, 2)
    seed = 2**31 + 11
    state = driver.build(cell, cfg, seed, jax.devices())
    step = cell.engine.chunk_fn(cfg, k)
    rows = []
    for _ in range(chunks):
        state, row = step(state)
        rows.append(row)
    final, series = run_sim(cfg, chunks * k, seed=seed, metrics_every=k)
    for field in series.__dataclass_fields__:
        got = np.concatenate([np.asarray(getattr(r, field)) for r in rows])
        np.testing.assert_array_equal(got, np.asarray(getattr(series, field)),
                                      err_msg=field)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(final)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
