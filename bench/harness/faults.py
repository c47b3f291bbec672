"""Faults planted under the timed path, each of which the check has to read
as not correct.

Five break a chunk call's result as the window receives it: the state
returned unchanged, half of the nodes' cache work left out, a payload lane
altered, a count altered, a modelled byte count altered.  ``wrong_victim``
breaks the program itself: the upsert evicts the way after the least
recently used one when a set is full, a fault that shows only once the
caches have filled.  ``planted`` switches one on for the chunks that an
engine module's ``chunk_fn`` builds; the tests plant each at a small size,
and ``bench/control.py --fault`` at a cell's own size on the chip.
"""
from __future__ import annotations

import contextlib
import dataclasses

CHUNK_FAULTS = ("state_unchanged", "half_the_nodes_left_out",
                "payload_altered", "count_altered", "bytes_altered")
PROGRAM_FAULTS = ("wrong_victim",)
FAULTS = CHUNK_FAULTS + PROGRAM_FAULTS


def _broken_chunk(kind, real):
    import jax
    import jax.numpy as jnp

    copy = jax.jit(lambda s: jax.tree.map(jnp.copy, s))

    def run(state):
        before = copy(state)
        new, row = real(state)
        if kind == "state_unchanged":
            return before, row
        if kind == "half_the_nodes_left_out":
            half = new.caches.tags.shape[0] // 2
            caches = jax.tree.map(lambda a, b: a.at[:half].set(b[:half]),
                                  new.caches, before.caches)
            return dataclasses.replace(new, caches=caches), row
        if kind == "payload_altered":
            c = new.caches
            bump = jnp.where(c.valid[0][..., None], jnp.float32(2**-20), 0)
            data = c.data.at[0].add(bump)
            return dataclasses.replace(
                new, caches=dataclasses.replace(c, data=data)), row
        if kind == "count_altered":
            return new, dataclasses.replace(row, hits_fog=row.hits_fog + 1)
        if kind == "bytes_altered":
            return new, dataclasses.replace(
                row, lan_bytes=row.lan_bytes * jnp.float32(1.001))
        raise ValueError(kind)

    return run


def _wrong_victim(real):
    import jax.numpy as jnp

    def select(tags_r, valid_r, use_r, keys):
        way, present = real(tags_r, valid_r, use_r, keys)
        full = jnp.all(valid_r, axis=1)
        wrong = (way + 1) % valid_r.shape[1]
        return jnp.where(full & ~present, wrong, way), present

    return select


@contextlib.contextmanager
def planted(kind: str, engine):
    """Within the block, the chunks that ``engine.chunk_fn`` builds (the
    module a cell's ``engine`` is) carry the fault ``kind``."""
    import jax

    from repro.core import flic

    if kind not in FAULTS:
        raise ValueError(f"unknown fault {kind!r}; known: {FAULTS}")
    real_chunk, real_select = engine.chunk_fn, flic._select_way_rows
    if kind == "wrong_victim":
        flic._select_way_rows = _wrong_victim(real_select)
    else:
        engine.chunk_fn = lambda cfg, k: _broken_chunk(kind, real_chunk(cfg, k))
    jax.clear_caches()
    try:
        yield
    finally:
        engine.chunk_fn, flic._select_way_rows = real_chunk, real_select
        jax.clear_caches()
