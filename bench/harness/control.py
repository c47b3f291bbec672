"""The correctness control, and the program's own readings, per seed.

The configuration states float32 payload lanes and float32 modelled bytes;
the control is the cell's engine's plain reference put in the program's
place with its payload tables stored, and its modelled row fields summed,
in bfloat16: the next precision down, and the narrowing a later change
would be tempted by (ROADMAP A4, "table widths").  Its rows, snapshot and
final state go through ``check.verify`` exactly as the timed path's do, and
it has to come out not correct.

``bench/control.py`` runs both at a cell's own size on the chip; the tests
run them at a small size on the CPU.
"""
from __future__ import annotations

from harness import check, driver


def control_numbers(cell, spec: dict, seed: int, snap_chunk: int):
    """The numbers ``verify`` gives the bfloat16 control: the cell's
    engine's reference."""
    import ml_dtypes

    engine = cell.engine
    rows, state, _ = check.replay(engine.Reference, spec, seed, snap_chunk,
                                  cell.chunk_ticks,
                                  payload_dtype=ml_dtypes.bfloat16)
    numbers, _, _ = check.verify(engine, rows, state, state, snap_chunk, spec,
                                 seed, cell.chunk_ticks)
    return numbers


def program_numbers(cfg, spec: dict, cell, seed: int, extra_chunks: int = 0):
    """The numbers ``verify`` gives the program: the cell's engine's chunk
    calls from tick 0 to the seed's snapshot chunk and ``extra_chunks``
    beyond it.  Returns (numbers, snapshot chunk, what the replay
    covered)."""
    import jax

    engine = cell.engine
    state = driver.build(cell, cfg, seed, jax.devices())
    chunk = engine.chunk_fn(cfg, cell.chunk_ticks)
    snap_chunk = driver.snapshot_chunk(cell, seed)
    rows, snapshot = [], None
    for i in range(snap_chunk + extra_chunks):
        state, row = chunk(state)
        rows.append(jax.device_get(row))
        if i + 1 == snap_chunk:
            snapshot = engine.state_leaves(state)
    numbers, _, info = check.verify(
        engine, check.rows_to_host(rows), snapshot, engine.state_leaves(state),
        snap_chunk, spec, seed, cell.chunk_ticks)
    return numbers, snap_chunk, info
