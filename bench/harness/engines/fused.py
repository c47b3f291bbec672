"""The fused engine on one chip: the program's ``simulator.sim_tick`` in one
jitted scan per chunk, and the plain reference that replays its single
PRNG stream.

An engine module gives the harness everything that depends on how the
program runs a configuration (``harness/cells.py`` finds it by the
configuration's ``engine`` key): ``build`` the initial state from the seed
on the cell's devices, ``chunk_fn`` one chunk call, ``lower`` the compiled
chunk program, ``state_leaves`` the state under the reference's names,
``Reference`` the plain reference, and ``writer_rings`` the writer rings
the engine drains.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness.reference import ReferenceSim

ENGINE = "fused"
Reference = ReferenceSim


def build(cfg, seed: int, devices):
    """The initial state for the seed, built on the device in one jitted
    call.  The seed enters as the initial PRNG key,
    ``jax.random.PRNGKey(seed)`` as in ``init_sim``, so every seed shares
    one compiled program."""
    import jax
    from repro.core import simulator

    (device,) = devices

    @jax.jit
    def init(key):
        return dataclasses.replace(simulator.init_sim(cfg), rng=key)

    with jax.default_device(device):
        return init(jax.random.PRNGKey(seed))


def chunk_fn(cfg, chunk_ticks: int):
    """``state -> (state, row)``: ``chunk_ticks`` ticks with the state
    donated, folded into one ``TickMetrics`` row."""
    from repro.core import simulator

    def run(state):
        return simulator._run_scan(cfg, chunk_ticks, state, chunk_ticks, ENGINE)

    return run


def lower(cfg, chunk_ticks: int, state):
    """The compiled chunk program (a compile-cache load after a run)."""
    from repro.core import simulator

    return simulator._run_scan.lower(cfg, chunk_ticks, state, chunk_ticks,
                                     ENGINE).compile()


def state_leaves(state) -> dict:
    """The program's ``SimState`` under the reference's names, on the host."""
    import jax

    c, q, s = state.caches, state.queue, state.store
    leaves = {
        "caches.tags": c.tags, "caches.data_ts": c.data_ts,
        "caches.ins_ts": c.ins_ts, "caches.origin": c.origin,
        "caches.valid": c.valid, "caches.dirty": c.dirty,
        "caches.last_use": c.last_use, "caches.data": c.data,
        "queue.keys": q.keys, "queue.data_ts": q.data_ts,
        "queue.origin": q.origin, "queue.head": q.head, "queue.tail": q.tail,
        "queue.dropped": q.dropped, "queue.backoff": q.backoff,
        "queue.next_retry": q.next_retry, "queue.tokens": q.tokens,
        "queue.slot_of_key": q.slot_of_key, "queue.coalesced": q.coalesced,
        "store.drained_total": s.drained_total, "store.api_calls": s.api_calls,
        "store.outage_until": s.outage_until, "store.lost_writes": s.lost_writes,
        "store.table_ts": s.table_ts, "tick": state.tick, "rng": state.rng,
        "latest_ts": state.latest_ts, "plan.cum_writes": state.plan.cum_writes,
    }
    return {k: np.asarray(v) for k, v in jax.device_get(leaves).items()}


def writer_rings(spec: dict) -> int:
    """One write-behind writer drains one ring."""
    return 1
