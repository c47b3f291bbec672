"""Functional FLIC cache operations: lookup, insert/update, LRU eviction.

These are the single-node primitives.  They are written against an unbatched
``CacheState`` ``(S, W)`` and are ``vmap``-ed over nodes by the simulator and
``shard_map``-ed over devices by the distributed runtime.

Semantics (paper §II):

* ``local_lookup`` — tag match within the key's set; on a hit the LRU stamp
  is refreshed.
* ``insert`` — soft-coherence aware upsert:
    - if the key is already present, overwrite *only if* the incoming
      ``data_ts`` is newer (max-timestamp wins — paper §I.A.a);
    - otherwise fill an invalid way, else evict the LRU way.  The evicted
      line is returned so the caller can enqueue a write-back.
* ``lookup_batch`` / ``insert_batch`` — scan/vmap conveniences.

Everything is branch-free (``jnp.where`` / indexed scatters) so it lowers to
clean XLA and is directly portable into the Pallas kernels in
``repro.kernels``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.cache_state import NULL_TAG, CacheLine, CacheState, set_index


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LookupResult:
    hit: jax.Array       # bool
    data_ts: jax.Array   # int32 (-1 on miss)
    origin: jax.Array    # int32 (-1 on miss)
    data: jax.Array      # (D,) zeros on miss


def _select_way(cache: CacheState, sidx: jax.Array, tag: jax.Array):
    """Return (way_to_write, present, present_way, lru_way) for a set."""
    set_tags = cache.tags[sidx]          # (W,)
    set_valid = cache.valid[sidx]        # (W,)
    match = set_valid & (set_tags == tag)
    present = jnp.any(match)
    present_way = jnp.argmax(match)      # first matching way

    # Victim choice: first invalid way, else least-recently-used way.
    any_invalid = jnp.any(~set_valid)
    invalid_way = jnp.argmax(~set_valid)
    use = jnp.where(set_valid, cache.last_use[sidx], jnp.iinfo(jnp.int32).max)
    lru_way = jnp.argmin(use)
    victim_way = jnp.where(any_invalid, invalid_way, lru_way)

    way = jnp.where(present, present_way, victim_way)
    return way, present, present_way, victim_way


def local_lookup(
    cache: CacheState, key: jax.Array, now: jax.Array, update_lru: bool = True
) -> tuple[CacheState, LookupResult]:
    """Probe the local cache for ``key``; refresh LRU on hit."""
    key = jnp.asarray(key, jnp.uint32)
    sidx = set_index(cache, key)
    set_tags = cache.tags[sidx]
    set_valid = cache.valid[sidx]
    match = set_valid & (set_tags == key)
    hit = jnp.any(match)
    way = jnp.argmax(match)

    res = LookupResult(
        hit=hit,
        data_ts=jnp.where(hit, cache.data_ts[sidx, way], -1),
        origin=jnp.where(hit, cache.origin[sidx, way], -1),
        data=jnp.where(hit, cache.data[sidx, way], jnp.zeros_like(cache.data[sidx, way])),
    )
    if update_lru:
        new_last = cache.last_use.at[sidx, way].set(
            jnp.where(hit, jnp.asarray(now, jnp.int32), cache.last_use[sidx, way])
        )
        cache = dataclasses.replace(cache, last_use=new_last)
    return cache, res


def insert(
    cache: CacheState, line: CacheLine, now: jax.Array
) -> tuple[CacheState, CacheLine]:
    """Soft-coherence upsert of one line. Returns (new_cache, evicted_line).

    The returned eviction is ``valid`` only when a *live* line was displaced
    (not overwritten in place) — and ``dirty`` tells the caller whether the
    backing store still needs it.  If ``line.valid`` is False the call is a
    no-op (used for masked/lost broadcasts).
    """
    key = jnp.asarray(line.key, jnp.uint32)
    now = jnp.asarray(now, jnp.int32)
    sidx = set_index(cache, key)
    way, present, _, _ = _select_way(cache, sidx, key)

    old_ts = cache.data_ts[sidx, way]
    # Soft coherence: if present, only a strictly newer timestamp overwrites.
    stale_incoming = present & (jnp.asarray(line.data_ts, jnp.int32) <= old_ts)
    do_write = jnp.asarray(line.valid) & ~stale_incoming

    # Eviction record: displaced a DIFFERENT live line (not an in-place update).
    displaced = do_write & ~present & cache.valid[sidx, way]
    evicted = CacheLine(
        key=jnp.where(displaced, cache.tags[sidx, way], NULL_TAG),
        data_ts=jnp.where(displaced, old_ts, -1),
        origin=jnp.where(displaced, cache.origin[sidx, way], -1),
        data=jnp.where(displaced, cache.data[sidx, way], jnp.zeros_like(line.data)),
        valid=displaced,
        dirty=displaced & cache.dirty[sidx, way],
    )

    def wr(field, value):
        if field.dtype == jnp.bool_:
            # Scattered as int32 words: on a TPU v5e this vmapped one-line
            # scatter into the bool ``valid`` table left 922 entries of a
            # (2000, 50, 4) table wrong, while the int32 tables came out
            # exact.
            return wr(field.astype(jnp.int32), value) != 0
        return field.at[sidx, way].set(jnp.where(do_write, value, field[sidx, way]))

    cache = CacheState(
        tags=wr(cache.tags, key),
        data_ts=wr(cache.data_ts, jnp.asarray(line.data_ts, jnp.int32)),
        ins_ts=wr(cache.ins_ts, now),
        origin=wr(cache.origin, jnp.asarray(line.origin, jnp.int32)),
        valid=wr(cache.valid, True),
        dirty=wr(cache.dirty, jnp.asarray(line.dirty)),
        last_use=wr(cache.last_use, now),
        data=cache.data.at[sidx, way].set(
            jnp.where(do_write, line.data, cache.data[sidx, way])
        ),
    )
    return cache, evicted


def insert_batch(
    cache: CacheState, lines: CacheLine, now: jax.Array
) -> tuple[CacheState, CacheLine]:
    """Sequentially upsert a batch of lines (leading axis R). Returns evictions.

    Sequential application (lax.scan) keeps same-set conflicts within one
    batch exact — matching the paper's per-packet processing order.
    """

    def step(c, ln):
        c, ev = insert(c, ln, now)
        return c, ev

    return jax.lax.scan(step, cache, lines)


# --------------------------------------------------------------------------
# Batched (per-node) primitives: one line / one key per cache, fully fused.
#
# These are the hot-path versions of ``insert`` / ``local_lookup`` for a
# *batched* ``CacheState`` with leading node axis N: each node i upserts or
# probes its own lane i.  Per field this lowers to ONE gather of the probed
# set row and ONE scatter-lean write — no vmap-of-scalar chains and no
# (N, S, W)-materializing one-hot selects (DESIGN.md §3).  The scatter form
# matters on CPU/TPU alike: each lane writes exactly ONE flat line index
# ``(node*S + set)*W + way`` — unique per lane, so the scatter carries the
# uniqueness hint and skips XLA's conflict-safe serialization; no-op lanes
# keep the masked out-of-bounds-drop trick (all routed to the single OOB
# slot, never applied).
# Semantics match ``insert``/``local_lookup`` exactly: first-matching-way on
# hit, first-invalid-else-LRU victim, strictly-newer timestamp overwrites.
# --------------------------------------------------------------------------

def _gather_rows(field: jax.Array, sidx: jax.Array) -> jax.Array:
    """field (N, S, W[, D]), sidx (N,) -> the probed set row (N, W[, D])."""
    idx = sidx.reshape(sidx.shape + (1,) * (field.ndim - 1))
    return jnp.take_along_axis(field, idx, axis=1)[:, 0]


def _select_way_rows(tags_r, valid_r, use_r, keys):
    """Vectorized ``_select_way`` over a leading batch axis.

    Inputs are gathered set rows (N, W) and keys (N,); returns
    (way, present) with the scalar routine's exact tie-breaks.
    """
    match = valid_r & (tags_r == keys[:, None])
    present = jnp.any(match, axis=1)
    present_way = jnp.argmax(match, axis=1)           # first matching way
    any_invalid = jnp.any(~valid_r, axis=1)
    invalid_way = jnp.argmax(~valid_r, axis=1)        # first invalid way
    use = jnp.where(valid_r, use_r, jnp.iinfo(jnp.int32).max)
    lru_way = jnp.argmin(use, axis=1)
    victim_way = jnp.where(any_invalid, invalid_way, lru_way)
    return jnp.where(present, present_way, victim_way), present


def insert_rows(
    caches: CacheState, lines: CacheLine, now: jax.Array,
    backend: str | None = None,
) -> tuple[CacheState, CacheLine | None]:
    """Upsert one line per node across a batched cache (leading axis N).

    Equivalent to ``jax.vmap(insert)(caches, lines)`` but built from one
    gather + one one-hot scatter per field.  Returns (caches, evictions)
    with evictions batched over N; masked lanes (``lines.valid`` False) are
    no-ops, exactly like the scalar path.

    ``backend`` "xla" | "interpret" | "pallas" dispatches the upsert through
    ``repro.kernels.ops.flic_insert`` (the ``kernels/flic_insert.py`` Pallas
    kernel fusing all eight per-field scatters into one VMEM-pinned pass, or
    its pure-jnp oracle) — selected by ``SimConfig.probe_backend`` /
    ``REPRO_KERNELS`` exactly like the probe and sweep kernels.  The kernel
    path returns ``evictions=None``: both engine call sites discard the
    eviction record, and skipping it is what lets the kernel donate every
    table buffer.  Callers that need evictions use the default backend.
    """
    if backend not in (None, "fused"):
        return _insert_rows_kernel(caches, lines, now, backend), None
    n = caches.tags.shape[0]
    s_sets, w_ways = caches.num_sets, caches.num_ways
    keys = jnp.asarray(lines.key, jnp.uint32)
    now = jnp.asarray(now, jnp.int32)
    sidx = (keys % jnp.uint32(s_sets)).astype(jnp.int32)            # (N,)

    tags_r = _gather_rows(caches.tags, sidx)          # (N, W)
    valid_r = _gather_rows(caches.valid, sidx)
    use_r = _gather_rows(caches.last_use, sidx)
    way, present = _select_way_rows(tags_r, valid_r, use_r, keys)

    rows = jnp.arange(n)
    old_ts = caches.data_ts[rows, sidx, way]
    old_valid = valid_r[rows, way]
    line_ts = jnp.asarray(lines.data_ts, jnp.int32)
    stale_incoming = present & (line_ts <= old_ts)
    do_write = jnp.asarray(lines.valid) & ~stale_incoming

    displaced = do_write & ~present & old_valid
    evicted = CacheLine(
        key=jnp.where(displaced, tags_r[rows, way], NULL_TAG),
        data_ts=jnp.where(displaced, old_ts, -1),
        origin=jnp.where(displaced, caches.origin[rows, sidx, way], -1),
        data=jnp.where(
            displaced[:, None], caches.data[rows, sidx, way],
            jnp.zeros_like(lines.data),
        ),
        valid=displaced,
        dirty=displaced & caches.dirty[rows, sidx, way],
    )

    # Scatter-lean write: each lane targets its own FLAT line index (no-op
    # lanes route to the shared out-of-bounds slot and are dropped).  Live
    # indices are unique by construction — one slot per lane — and the
    # dropped ones are never applied, so the uniqueness hint is sound; it
    # lets XLA skip the conflict-safe serialization of the general scatter.
    flat = jnp.where(do_write, (rows * s_sets + sidx) * w_ways + way,
                     n * s_sets * w_ways)

    def wr(field, value):
        return field.reshape(-1).at[flat].set(
            value.astype(field.dtype), mode="drop", unique_indices=True
        ).reshape(field.shape)

    caches = CacheState(
        tags=wr(caches.tags, keys),
        data_ts=wr(caches.data_ts, line_ts),
        ins_ts=wr(caches.ins_ts, jnp.full((n,), now)),
        origin=wr(caches.origin, jnp.asarray(lines.origin, jnp.int32)),
        valid=wr(caches.valid, jnp.ones((n,), bool)),
        dirty=wr(caches.dirty, jnp.asarray(lines.dirty)),
        last_use=wr(caches.last_use, jnp.full((n,), now)),
        data=caches.data.reshape(n * s_sets * w_ways, -1).at[flat].set(
            lines.data, mode="drop", unique_indices=True
        ).reshape(caches.data.shape),
    )
    return caches, evicted


def _insert_rows_kernel(
    caches: CacheState, lines: CacheLine, now, backend
) -> CacheState:
    """Kernel-backed ``insert_rows`` upsert via ``repro.kernels.ops``.

    Unlike the probe/sweep kernels (vmapped per cache), ``flic_insert`` is
    natively batched over the node axis: one ``pallas_call`` walks node
    blocks and each node touches only its own probed set row, so all eight
    tables are donated whole.  Bool tables travel as int32 (TPU-lowerable)
    and are converted back here, exactly like the sweep kernel path.
    """
    from repro.kernels import ops

    keys = jnp.asarray(lines.key, jnp.uint32)
    sidx = (keys % jnp.uint32(caches.num_sets)).astype(jnp.int32)
    (tags, data_ts, ins_ts, origin, valid, dirty, last_use, data) = ops.flic_insert(
        caches.tags.astype(jnp.int32), caches.data_ts, caches.ins_ts,
        caches.origin, caches.valid, caches.dirty, caches.last_use,
        caches.data,
        keys.astype(jnp.int32), sidx,
        jnp.asarray(lines.data_ts, jnp.int32),
        jnp.asarray(lines.origin, jnp.int32),
        jnp.asarray(lines.dirty),
        jnp.asarray(lines.valid),
        lines.data,
        jnp.asarray(now, jnp.int32),
        backend=backend,
    )
    return CacheState(
        tags=tags.astype(jnp.uint32), data_ts=data_ts, ins_ts=ins_ts,
        origin=origin, valid=valid, dirty=dirty, last_use=last_use, data=data,
    )


def lookup_rows(
    caches: CacheState, keys: jax.Array, now: jax.Array, update_lru: bool = True
) -> tuple[CacheState, LookupResult]:
    """Probe one key per node across a batched cache (leading axis N).

    Equivalent to ``jax.vmap(local_lookup)`` with one gather per field and a
    single sorted-unique flat-index LRU scatter.
    """
    n = caches.tags.shape[0]
    keys = jnp.asarray(keys, jnp.uint32)
    sidx = (keys % jnp.uint32(caches.num_sets)).astype(jnp.int32)
    tags_r = _gather_rows(caches.tags, sidx)
    valid_r = _gather_rows(caches.valid, sidx)
    match = valid_r & (tags_r == keys[:, None])
    hit = jnp.any(match, axis=1)
    way = jnp.argmax(match, axis=1)

    rows = jnp.arange(n)
    res = LookupResult(
        hit=hit,
        data_ts=jnp.where(hit, caches.data_ts[rows, sidx, way], -1),
        origin=jnp.where(hit, caches.origin[rows, sidx, way], -1),
        data=jnp.where(
            hit[:, None], caches.data[rows, sidx, way],
            jnp.zeros_like(caches.data[rows, sidx, way]),
        ),
    )
    if update_lru:
        oob = n * caches.num_sets * caches.num_ways
        flat = jnp.where(
            hit, (rows * caches.num_sets + sidx) * caches.num_ways + way, oob
        )
        caches = dataclasses.replace(
            caches,
            last_use=caches.last_use.reshape(-1).at[flat].set(
                jnp.full((n,), jnp.asarray(now, jnp.int32)),
                mode="drop", unique_indices=True,
            ).reshape(caches.last_use.shape),
        )
    return caches, res


def update_rows(
    caches: CacheState,
    rows: CacheLine,
    delivered: jax.Array,
    now: jax.Array,
    node_ids: jax.Array | None = None,
    backend: str | None = None,
) -> tuple[CacheState, jax.Array]:
    """Batched coherence-update sweep: R broadcast rows against N caches.

    The directory policy's coherence traffic (paper §I.A.a): every hearer
    that already HOLDS a broadcast key updates its resident copy in place iff
    the incoming ``data_ts`` is strictly newer — no insert, no eviction.

    Inline formulation (``backend`` None/"fused"): one (N, R, W) gather per
    probed field, then ONE scatter-max electing the winning row index per
    cache line (``winr``), then dense per-line selects — no (N, R)-indexed
    scatters, which XLA serializes element-wise on CPU.  The winner among
    several qualifying rows for one line is the HIGHEST row index; every
    shipped workload makes duplicate rows value-identical (same tick ⇒ same
    ts, payloads pure in (key, ts) — ``workload.versioned_payload``), so the
    tie-break is unobservable there.  ``backend`` "xla" | "interpret" |
    "pallas" dispatches the sweep through ``repro.kernels.ops.flic_update``
    (the ``kernels/flic_update.py`` Pallas kernel or its pure-jnp oracle,
    same winner semantics) — selected by ``SimConfig.probe_backend`` /
    ``REPRO_KERNELS`` exactly like the fog-probe kernel.

    ``delivered`` is (N, R) per-(hearer, row) delivery under the loss model;
    a row is always applied at its origin.  ``node_ids`` maps local cache
    lanes to global node ids (the distributed runtime passes the shard's).

    Returns (caches, n_updates) — the number of in-place updates applied
    (counted per qualifying (hearer, row) pair against the PRE-sweep
    timestamps, on every backend), which the simulator reports as
    ``coherence_updates``.  On write-once workloads this pass is a provable
    no-op and the fused engine skips it; mutable workloads run it every
    tick.  The no-op claim holds up to 32-bit tag collisions between rows
    resident at the same hearer (expected colliding pairs ~ rows²/2³³ —
    ≪1 for every shipped test/benchmark scale); a collision would make the
    engines diverge on that line only.
    """
    n = caches.tags.shape[0]
    if node_ids is None:
        node_ids = jnp.arange(n, dtype=jnp.int32)
    keys = jnp.asarray(rows.key, jnp.uint32)                            # (R,)
    r = keys.shape[0]
    sidx = (keys % jnp.uint32(caches.num_sets)).astype(jnp.int32)       # (R,)
    row_ts = jnp.asarray(rows.data_ts, jnp.int32)

    is_origin = jnp.asarray(rows.origin, jnp.int32)[None, :] == node_ids[:, None]
    live = jnp.asarray(rows.valid)[None, :] & (delivered | is_origin)   # (N, R)

    if backend not in (None, "fused"):
        return _update_rows_kernel(
            caches, keys, sidx, row_ts, rows.data, live, now, backend
        )

    set_tags = caches.tags[:, sidx]                                     # (N, R, W)
    set_valid = caches.valid[:, sidx]
    match = set_valid & (set_tags == keys[None, :, None])
    newer = row_ts[None, :, None] > caches.data_ts[:, sidx]
    upd = match & newer & live[:, :, None]                              # (N, R, W)
    n_upd = jnp.sum(jnp.any(upd, axis=2).astype(jnp.int32))

    # Winning row per line: scatter-max of the row index along the shared
    # set-index vector (R slice-updates vectorized over nodes), then dense
    # gathers of the winners' values — never an (N, R)-indexed scatter.
    ridx = jnp.arange(r, dtype=jnp.int32)
    winr = jnp.full(caches.tags.shape, -1, jnp.int32).at[:, sidx].max(
        jnp.where(upd, ridx[None, :, None], -1)
    )
    updated = winr >= 0                                                 # (N, S, W)
    wsafe = jnp.maximum(winr, 0)
    caches = dataclasses.replace(
        caches,
        data_ts=jnp.where(updated, row_ts[wsafe], caches.data_ts),
        last_use=jnp.where(updated, jnp.asarray(now, jnp.int32), caches.last_use),
        data=jnp.where(updated[..., None], rows.data[wsafe], caches.data),
    )
    return caches, n_upd


def _update_rows_kernel(
    caches: CacheState, keys, sidx, row_ts, row_data, live, now, backend
) -> tuple[CacheState, jax.Array]:
    """Kernel-backed ``update_rows`` sweep via ``repro.kernels.ops``.

    Pads the row axis to the kernel block, vmaps the per-cache kernel over
    the node axis, and reassembles the cache pytree.  Padding rows carry
    ``live=False`` so they can never apply.
    """
    from repro.kernels import ops

    n = caches.tags.shape[0]
    r = keys.shape[0]
    rb = min(ops.FLIC_UPDATE_BLOCK, r)
    pad = (-r) % rb
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), NULL_TAG)])
        sidx = jnp.concatenate([sidx, jnp.zeros((pad,), jnp.int32)])
        row_ts = jnp.concatenate([row_ts, jnp.full((pad,), -1, jnp.int32)])
        row_data = jnp.concatenate(
            [row_data, jnp.zeros((pad, row_data.shape[-1]), row_data.dtype)]
        )
        live = jnp.concatenate([live, jnp.zeros((n, pad), bool)], axis=1)
    now_i = jnp.full((1,), jnp.asarray(now, jnp.int32))

    def one_cache(tags, data_ts, valid, last_use, data, live_n):
        return ops.flic_update(
            tags, data_ts, valid, last_use, data,
            keys.astype(jnp.int32), sidx, row_ts,
            row_data, live_n, now_i, backend=backend,
        )

    new_ts, new_lu, new_data, cnt = jax.vmap(one_cache)(
        caches.tags.astype(jnp.int32), caches.data_ts,
        caches.valid, caches.last_use, caches.data, live,
    )
    caches = dataclasses.replace(
        caches, data_ts=new_ts, last_use=new_lu, data=new_data
    )
    return caches, jnp.sum(cnt)


def invalidate_nodes(caches: CacheState, node_mask: jax.Array) -> CacheState:
    """Cold-start the caches of the masked nodes (churn rejoin, §III churn).

    ``node_mask`` is (N,) over the leading batch axis; masked nodes lose every
    line (valid=False) — tags/data are left in place but unreachable.
    """
    keep = ~jnp.asarray(node_mask, bool)
    return dataclasses.replace(
        caches, valid=caches.valid & keep[:, None, None]
    )


def invalidate(cache: CacheState, key: jax.Array) -> CacheState:
    """Drop a key if present (used by serving page-free paths)."""
    key = jnp.asarray(key, jnp.uint32)
    sidx = set_index(cache, key)
    match = cache.valid[sidx] & (cache.tags[sidx] == key)
    new_valid = cache.valid.at[sidx].set(cache.valid[sidx] & ~match)
    return dataclasses.replace(cache, valid=new_valid)


# --------------------------------------------------------------------------
# Fog-level (multi-node) read: the paper's broadcast query.
# --------------------------------------------------------------------------

def fog_lookup(
    caches: CacheState,
    key: jax.Array,
    now: jax.Array,
    respond_mask: jax.Array | None = None,
) -> tuple[CacheState, LookupResult, jax.Array]:
    """Broadcast-read ``key`` against all N node caches (leading axis N).

    Returns (caches, best_result, responders):
      * ``best_result`` — soft coherence pick: among responding hits, the one
        with the max data timestamp (paper §I.A.a).
      * ``responders`` — (N,) bool, which nodes had the line (paper's read
        simulator "keeps track of whichever nodes had the value").

    ``respond_mask`` models lost request/response packets (None = reliable).
    LRU is refreshed on every responder that hit, mirroring a served read.
    """
    n = caches.tags.shape[0]
    caches, results = lookup_rows(
        caches, jnp.full((n,), jnp.asarray(key, jnp.uint32)), now
    )
    hits = results.hit
    if respond_mask is not None:
        hits = hits & respond_mask
    responders = hits

    ts = jnp.where(hits, results.data_ts, -1)
    best = jnp.argmax(ts)  # ties → lowest node id, deterministic
    any_hit = jnp.any(hits)
    best_res = LookupResult(
        hit=any_hit,
        data_ts=jnp.where(any_hit, ts[best], -1),
        origin=jnp.where(any_hit, results.origin[best], -1),
        data=jnp.where(any_hit, results.data[best], jnp.zeros_like(results.data[0])),
    )
    del n
    return caches, best_res, responders
