"""Decide ``correct``: the timed path's output against the plain reference,
and the traffic's own arithmetic over the whole window.

Every number compared is an exact count of disagreements, with the limit 0,
except ``float_gap``: the largest relative gap of a modelled-bytes or
modelled-latency row field, which the program sums in float32.

* ``row_mismatch``: integer fields of the chunk rows, from tick 0 through the
  snapshot chunk, that differ from the reference replay of those chunks;
* ``state_mismatch``: elements of the state after the snapshot chunk (every
  cache table, payload lanes included, the writer ring, the store, the
  PRNG key) that differ from the reference's;
* ``invariant_violations``: over every chunk of the run, reads against the
  read schedule, writes against one per node per tick, the read partition
  (local + fog + ring + store), drain batches within the bound of the
  engine's writers, and at the end the conservation of writes (drained +
  pending + dropped + coalesced, summed over the writer rings) and the
  state's tick;
* ``bad_lines``: valid lines of the final caches whose key lies in the wrong
  set, whose payload is not the one its key (and version) derive, whose
  version is from the future, or (Zipf keys) whose key is no key id's hash.
"""
from __future__ import annotations

import time

import numpy as np

from harness import reference as ref

LIMITS = {
    "row_mismatch": 0,
    "float_gap": 1e-5,
    "state_mismatch": 0,
    "invariant_violations": 0,
    "bad_lines": 0,
}


def rows_to_host(rows) -> list[dict]:
    """Chunk rows (``TickMetrics`` with a leading axis of 1) as dicts."""
    out = []
    for row in rows:
        out.append({f: np.asarray(getattr(row, f)).reshape(-1)[0]
                    for f in ref.INT_FIELDS + ref.FLOAT_FIELDS})
    return out


def _gap(a, b) -> float:
    a, b = float(a), float(b)
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(b), 1.0)


def compare_rows(rows: list[dict], ref_rows: list[dict]) -> tuple[int, float, set]:
    """(integer fields that differ, largest float gap, chunks that differ)."""
    mismatch, gap, bad = 0, 0.0, set()
    for i, (a, b) in enumerate(zip(rows, ref_rows)):
        wrong = sum(int(a[f]) != int(b[f]) for f in ref.INT_FIELDS)
        g = max(_gap(a[f], b[f]) for f in ref.FLOAT_FIELDS)
        mismatch += wrong
        gap = max(gap, g)
        if wrong or g > LIMITS["float_gap"]:
            bad.add(i)
    return mismatch, gap, bad


def compare_state(got: dict, want: dict) -> tuple[int, list]:
    """Elements that differ, and the leaves they are in.  Payload lanes are
    compared as the stored bits widened to float32."""
    total, where = 0, []
    for name, w in want.items():
        g = np.asarray(got[name])
        w = np.asarray(w)
        if g.shape != w.shape:
            total += max(g.size, w.size)
            where.append(name)
            continue
        if g.dtype.kind == "f" or w.dtype.kind == "f":
            diff = int(np.sum(g.astype(np.float32).view(np.uint32)
                              != w.astype(np.float32).view(np.uint32)))
        else:
            diff = int(np.sum(g.astype(np.int64) != w.astype(np.int64)))
        if diff:
            total += diff
            where.append(name)
    return total, where


def expected_reads(n: int, period: int, t0: int, t1: int) -> int:
    t = np.arange(t0, t1)[:, None]
    node = np.arange(n)[None, :]
    return int((((t + node) % period == 0) & (t > 0)).sum())


def _total(final: dict, name: str) -> int:
    """A leaf summed over its leading ring axis, where it has one."""
    return int(np.sum(np.asarray(final[name], np.int64)))


def invariants(rows: list[dict], final: dict, spec: dict, chunk_ticks: int,
               rings: int):
    """(violations, chunks that violate).  ``final`` is the final state's
    leaves; rows cover every chunk from tick 0.  ``rings`` writers drain
    ``rings`` rings, each up to ``writer_max_per_tick`` rows a tick; the
    ring and store leaves of ``final`` carry a leading axis of ``rings``
    where that is more than one."""
    sim = spec["sim"]
    n, period = int(sim["n_nodes"]), int(sim["read_period"])
    max_drain = rings * int(sim["writer_max_per_tick"])
    bad, violations = set(), 0
    for i, r in enumerate(rows):
        t0 = i * chunk_ticks
        checks = [
            int(r["ticks"]) == chunk_ticks,
            int(r["reads"]) == expected_reads(n, period, t0, t0 + chunk_ticks),
            int(r["writes_gen"]) == n * chunk_ticks,
            int(r["reads"]) == int(r["hits_local"]) + int(r["hits_fog"])
            + int(r["hits_queue"]) + int(r["misses"]),
            int(r["store_found"]) + int(r["store_missing"]) == int(r["misses"]),
            0 <= int(r["writes_drained"]) <= max_drain * chunk_ticks,
            int(r["stale_reads"]) <= int(r["reads"]),
        ]
        wrong = checks.count(False)
        if wrong:
            violations += wrong
            bad.add(i)
    total = {f: sum(int(r[f]) for r in rows)
             for f in ("writes_gen", "writes_drained", "writes_coalesced")}
    last = rows[-1]
    final_checks = [
        total["writes_gen"] == total["writes_drained"] + int(last["queue_depth"])
        + int(last["queue_dropped"]) + total["writes_coalesced"],
        int(final["tick"]) == len(rows) * chunk_ticks,
        _total(final, "store.drained_total") + _total(final, "store.lost_writes")
        == total["writes_drained"],
        _total(final, "queue.tail") - _total(final, "queue.head")
        == int(last["queue_depth"]),
        _total(final, "queue.dropped") == int(last["queue_dropped"]),
    ]
    if False in final_checks:
        violations += final_checks.count(False)
        bad.add(len(rows) - 1)
    return violations, bad


def bad_lines(final: dict, spec: dict) -> int:
    """Valid lines of the final caches that contradict their own key."""
    sim, wl = spec["sim"], spec["workload"]
    sets = int(sim["cache_lines"]) // int(sim["cache_ways"])
    dim = int(sim["payload_dim"])
    valid = final["caches.valid"].astype(bool)
    keys = final["caches.tags"][valid]
    ts = final["caches.data_ts"][valid]
    data = final["caches.data"][valid].astype(np.float32)
    set_of_line = np.nonzero(valid)[1]
    wrong = (keys % np.uint32(sets)).astype(np.int64) != set_of_line
    wrong |= ts >= int(final["tick"])
    # Many lines hold the same row: derive each distinct payload once.
    if wl.get("popularity", "stream") == "zipf":
        pairs = keys.astype(np.uint64) << np.uint64(32) | ts.astype(np.uint32)
        uniq, inverse = np.unique(pairs, return_inverse=True)
        want = ref.versioned_payload((uniq >> np.uint64(32)).astype(np.uint32),
                                     (uniq & np.uint64(0xFFFFFFFF)).astype(
                                         np.uint32).view(np.int32), dim)
        ids = ref.key_of_id(np.arange(int(wl["key_universe"])))
        wrong |= ~np.isin(keys, ids)
    else:
        uniq, inverse = np.unique(keys, return_inverse=True)
        want = ref.payload(uniq, dim)
    wrong |= np.any(data.view(np.uint32) != want.view(np.uint32)[inverse], axis=1)
    return int(wrong.sum())


def replay(reference, spec: dict, seed: int, chunks: int, chunk_ticks: int,
           payload_dtype=np.float32):
    """The rows of the plain reference (an engine's ``Reference`` class) for
    the first ``chunks`` chunks, its state, and what the replay covered:
    ticks, LRU evictions (all of them, and in the last quarter of the ticks,
    where the caches are full) and seconds."""
    t0 = time.perf_counter()
    sim = reference(spec, seed, payload_dtype=payload_dtype)
    rows = [sim.chunk(chunk_ticks) for _ in range(chunks - chunks // 4)]
    early = sim.evictions
    rows += [sim.chunk(chunk_ticks) for _ in range(chunks // 4)]
    info = {"ticks": sim.t, "evictions": sim.evictions,
            "evictions_last_quarter": sim.evictions - early,
            "seconds": time.perf_counter() - t0}
    return rows, sim.state(), info


def verify(engine, rows: list[dict], snapshot: dict, final: dict,
           snap_chunk: int, spec: dict, seed: int, chunk_ticks: int):
    """Returns (numbers compared, chunks that failed, what the replay
    covered), against ``engine``'s reference and writer rings.  A chunk
    fails when its row does; the snapshot chunk when the state differs; the
    last chunk when the final state breaks an invariant or holds a bad
    line."""
    ref_rows, ref_state, info = replay(engine.Reference, spec, seed,
                                      snap_chunk, chunk_ticks)
    row_mis, fgap, bad = compare_rows(rows[:snap_chunk], ref_rows)
    st_mis, _ = compare_state(snapshot, ref_state)
    inv, inv_bad = invariants(rows, final, spec, chunk_ticks,
                              engine.writer_rings(spec))
    numbers = {
        "row_mismatch": row_mis,
        "float_gap": fgap,
        "state_mismatch": st_mis,
        "invariant_violations": inv,
        "bad_lines": bad_lines(final, spec),
    }
    failed = bad | inv_bad
    if st_mis:
        failed.add(snap_chunk - 1)
    if numbers["bad_lines"]:
        failed.add(len(rows) - 1)
    return numbers, failed, info


def correct(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
