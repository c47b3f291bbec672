"""Vectorized N-node fog simulation of FLIC under ``lax.scan``.

This reproduces the paper's Docker testbed (§III) exactly in semantics but
as a single JAX program: all N node caches are a batched ``CacheState``;
ticks are 1 s; each node writes one fresh row per tick and issues one read
every ``read_period`` ticks; the single queued writer drains to a simulated
cloud store under rate limiting and failures.

Workload model (from §III-B, with ambiguities resolved — see DESIGN.md §2);
the DEFAULT scenario below is the paper's; ``SimConfig.workload`` selects
alternative scenarios from ``repro.core.workload`` (DESIGN.md §7):

* Writes: node ``n`` at tick ``t`` generates row key = hash(t, n), broadcast
  to the fog.  **Insert policy** (config):
    - ``"directory"`` (default): the payload is cached at the ORIGIN node
      (and later at read-fillers); hearers record the key in their key
      directory and apply coherence *updates* to copies they already hold.
      This matches the paper's Fig. 3/4 scaling (fog capacity grows with N).
    - ``"replicate"``: every hearer inserts the full row (ablation mode).
* Reads: every ``read_period`` ticks (staggered by node id), a node samples
  a key uniformly from its directory — the last ``read_window_keys`` keys it
  heard fog-wide, i.e. ages ~ U[0, window_keys/N] ticks ("preferentially
  reading recent data", §III-B).  Read path: local -> fog broadcast ->
  writer buffer -> store.  Fills on fog/store hits land in the reader's
  local cache.
* The store holds exactly the first ``drained_total`` enqueued rows (FIFO
  single writer), so durability of row (t, n) is the integer test
  ``t*N + n < drained_total``.  (Exact while the ring never overflows; with
  injected outages the tiny overflow tail is counted in ``queue_dropped``.)
* Fault tolerance (§VI): rows still pending in the writer's ring are
  readable from the fog (store-to-load forwarding on the paper's
  "load-store buffer"); while the store is DOWN the writer also forwards
  already-drained rows that remain physically resident in its ring, and
  synchronous store reads are not attempted (the store is unreachable).

Workload generation is NOT in this module: every engine consumes the same
per-tick ``RequestPlan`` from ``workload.plan_tick`` (the plan/execute
split, DESIGN.md §7) — writes and reads arrive as fixed-shape padded
tensors (keys, validity masks, rejoin/online masks, durability indices),
and the engines only *execute* them.  This module holds the FUSED engine
(DESIGN.md §3): one batched probe serves the local-hit check, the fog
broadcast query, and the responder LRU-touch scatter; inserts are the
batched ``insert_rows`` primitive; the per-tick coherence-update pass is
skipped when workload keys are write-once and runs as the batched
``flic.update_rows`` sweep when the scenario can re-write
(``WorkloadSpec.mutable``).  Mutable scenarios also swap the FIFO-index
durability arithmetic for the keyed versioned-membership model
(``_resolve_backstop_keyed`` / ``backing_store.table_ts``) with
load-store-buffer coalescing in the writer's ring (``wb.enqueue_keyed``);
stream scenarios with churn/rate modulation use the plan's carried
cumulative-write ring index (``workload.PlanState``) instead of the closed
form.  The reference engine in ``simulator_ref.py`` retains the seed's
per-pass structure, and ``tests/test_sim_equivalence.py`` proves both emit
identical metrics on every scenario.  The function is pure; everything
(losses, outages, workload) is driven by a single PRNG key, so runs are
exactly reproducible.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core import backing_store as bs
from repro.core import workload as wl
from repro.core import writeback as wb
from repro.core.cache_state import NULL_TAG, CacheLine, CacheState, empty_cache
from repro.core.coherence import (
    GilbertElliott,
    bernoulli_loss_mask,
    gilbert_elliott_advance,
    gilbert_elliott_mask,
)
from repro.core.flic import insert_rows, invalidate_nodes, pick_rows, update_rows
from repro.core.metrics import TickMetrics, windowed_scan

# Payload derivation lives in the workload layer now; keep the old name —
# the reference engine and distributed runtime import it from here.
_payload_for = wl.payload_for


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static configuration of one fog simulation."""

    n_nodes: int = 50
    cache_lines: int = 200           # per-node capacity (paper's "cache size")
    cache_ways: int = 4              # set-associativity
    payload_dim: int = 8             # payload lanes materialized in sim
    row_bytes: int = 148             # wire size of one row (payload+metadata)
    query_bytes: int = 32            # fog read-request packet
    read_period: int = 15            # paper: one read per 15 s per node
    read_window_keys: int = 2000     # reader's key-directory depth (in keys)
    loss_model: Literal["none", "bernoulli", "gilbert_elliott"] = "bernoulli"
    loss_prob: float = 0.02          # per-(receiver,packet) UDP loss
    insert_policy: Literal["directory", "replicate"] = "directory"
    queue_capacity: int = 8192
    writer_max_per_tick: int = 64
    store: bs.StoreProfile = dataclasses.field(default_factory=bs.StoreProfile)
    # Deterministic store-outage windows ((start_tick, duration), ...): at
    # ``t == start`` the store goes down for ``duration`` ticks.  Static, so
    # one failure trace drives every engine identically inside lax.scan (the
    # conformance matrix's §VI fault-tolerance schedules); () = no outages.
    outage_schedule: tuple[tuple[int, int], ...] = ()
    # Fog-probe backend (DESIGN.md §4): None/"fused" = inline jnp gathers;
    # "xla" | "interpret" | "pallas" dispatch through repro.kernels.ops.
    # NB: the kernel backends break soft-coherence ties by max-data_ts way,
    # the inline path by first-matching-way — identical on any state
    # reachable via insert/insert_rows (one copy of a key per set).
    probe_backend: Optional[str] = None
    # Scenario selection (workload.SCENARIOS has named presets); the default
    # spec is the paper's write-once stream and keeps the PR-1 fast paths.
    workload: wl.WorkloadSpec = dataclasses.field(default_factory=wl.WorkloadSpec)
    # Modeled latency terms (ticks == seconds), for the Fig. 2 reproduction.
    lat_local: float = 1e-4
    lat_lan_base: float = 2e-3
    lat_lan_per_node: float = 1.2e-4   # paper's Docker CPU-contention artifact
    lat_store: float = 1.1
    seed: int = 0

    @property
    def cache_sets(self) -> int:
        assert self.cache_lines % self.cache_ways == 0, "lines % ways != 0"
        return self.cache_lines // self.cache_ways

    @property
    def window_ticks(self) -> int:
        return max(1, round(self.read_window_keys / self.n_nodes))

    @property
    def readers_per_tick(self) -> int:
        """Static bound on simultaneous readers.  The staggered schedule
        activates exactly the nodes ≡ -t (mod read_period); trace replay
        can make any subset read, so its bound is N."""
        if self.workload.popularity == "trace":
            return self.n_nodes
        return -(-self.n_nodes // self.read_period)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimState:
    caches: CacheState          # batched (N, S, W, ...)
    queue: wb.WriteQueue
    store: bs.StoreState
    channel: GilbertElliott     # used only under the GE loss model
    tick: jax.Array             # int32
    rng: jax.Array
    latest_ts: jax.Array        # (K,) int32 — newest write tick per key id
    #                             (mutable workloads; ground truth for the
    #                              staleness metric); (0,) for stream
    plan: wl.PlanState          # carried plan-stage state (cumulative-write
    #                             ring indexing; empty shapes when unused)


def init_sim(cfg: SimConfig) -> SimState:
    ku = cfg.workload.key_universe if cfg.workload.mutable else 0
    return SimState(
        caches=empty_cache(
            cfg.cache_sets, cfg.cache_ways, cfg.payload_dim, jnp.float32,
            batch=(cfg.n_nodes,),
        ),
        queue=wb.empty_queue(cfg.queue_capacity, key_universe=ku),
        store=bs.init_store(key_universe=ku),
        channel=GilbertElliott.init(cfg.n_nodes),
        tick=jnp.int32(0),
        rng=jax.random.PRNGKey(cfg.seed),
        latest_ts=jnp.full((ku,), -1, jnp.int32),
        plan=wl.init_plan_state(cfg),
    )


# --------------------------------------------------------------------------
# The R-compact PRNG schedule (DESIGN.md §9), shared by all three engines.
#
# Per tick the channel advances exactly ONCE (`_advance_channel`, from
# ``k_deliver``); every mask is then a stateless draw against the advanced
# channel.  The write-delivery mask is drawn only when a consumer exists
# (mutable coherence sweep or replicate merge — `_needs_delivery_mask`); the
# response-loss mask is drawn over the R reader-compaction rows, never
# (n, n).  Under ``WorkloadSpec.fanout`` both masks compact further to the
# K neighbor lanes, and the dense engines expand them by scatter.
# --------------------------------------------------------------------------

def _advance_channel(cfg: SimConfig, channel, k_deliver):
    """Advance the GE channel once per tick; returns (channel, k_mask).

    ``k_mask`` seeds the tick's write-delivery mask (when drawn).  For the
    stateless loss models the channel is untouched and ``k_deliver`` is the
    mask key itself.
    """
    if cfg.loss_model == "gilbert_elliott":
        return gilbert_elliott_advance(channel, k_deliver)
    return channel, k_deliver


def _loss_mask(cfg: SimConfig, channel, rng, shape, receivers=None):
    """A loss mask over ``shape`` against an ALREADY-advanced channel.

    ``shape[0]`` indexes receivers; ``receivers`` maps compact leading rows
    (e.g. reader slots) to global node ids for the GE per-receiver loss
    probability.  True = delivered.
    """
    if cfg.loss_model == "none":
        return jnp.ones(shape, bool)
    if cfg.loss_model == "bernoulli":
        return bernoulli_loss_mask(rng, shape, cfg.loss_prob)
    return gilbert_elliott_mask(channel, rng, shape, receivers=receivers)


def _needs_delivery_mask(cfg: SimConfig) -> bool:
    """Whether anything consumes the write-delivery mask this scenario.

    The mutable coherence sweep and the replicate merge do; the write-once
    directory path provably never reads it (the sweep is a no-op), so those
    scenarios skip the draw entirely (DESIGN.md §9).
    """
    return cfg.insert_policy != "directory" or cfg.workload.mutable


def _neighbor_index(cfg: SimConfig):
    """The static (N, K) ring neighbor table, or None when gossip is dense."""
    if cfg.workload.fanout is None:
        return None
    return jnp.asarray(wl.neighbor_table(cfg.n_nodes, cfg.workload.fanout))


def _node_minor(caches):
    """Pin the (N, S, W) tables to a node-minor layout (the node axis in
    the TPU's 128 lanes, the 4 ways in sublanes).  Left to itself the
    compiler may hold them W-minor, padding 4 ways to 128 lanes: 32 times
    the bytes in every pass over a table (PERF.md §6)."""
    node_minor = Layout((1, 2, 0))
    return dataclasses.replace(caches, **{
        f: with_layout_constraint(getattr(caches, f), node_minor)
        for f in ("tags", "data_ts", "ins_ts", "origin", "valid", "dirty", "last_use")
    })


def _ring_lanes(cfg: SimConfig, sidx):
    """The fan-out probe's reads of the cache tables, as whole-table passes.

    Returns ``lanes(table) -> (N, K+1, W)`` with ``lanes(table)[i, j] =
    table[(i + off_j) mod N, sidx[i]]``: lane 0 is node i itself, lane j its
    ring neighbour ``nbr[i, j-1]``.  Each lane is a static roll along the
    node axis and a masked reduce over the sets, exact for every dtype.  A
    gather of the lanes' set rows would make the compiler hold every
    ``(N, S, W)`` table W-minor on the TPU (4 ways padded to 128 lanes),
    and element gathers cost ~12 ns each there (PERF.md §6).
    """
    nbr0 = wl.neighbor_table(cfg.n_nodes, cfg.workload.fanout)[0]
    offs = [0] + [int(c) for c in nbr0]                   # off_j mod N
    sets = jax.lax.iota(jnp.int32, cfg.cache_sets)

    def lanes(table):
        # q[j] = table[j, sidx[j - off]], so q[i + off] = table[i + off, sidx[i]].
        return jnp.stack([
            jnp.roll(pick_rows(table, sets[None, :, None]
                               == jnp.roll(sidx, off)[:, None, None]), -off, axis=0)
            for off in offs
        ], axis=1)

    return lanes


def _expand_lanes_dense(lanes, nbr, n: int):
    """Scatter (N, K) per-neighbor-lane values into a dense (N, n) mask.

    Cell (i, nbr[i, k]) takes lanes[i, k]; non-neighbor cells are False —
    the dense engines consume exactly the fused engine's K-lane draws, so
    conformance holds bitwise under fanout.
    """
    base = jnp.zeros((lanes.shape[0], n), lanes.dtype)
    rows = jnp.arange(lanes.shape[0], dtype=jnp.int32)[:, None]
    return base.at[rows, nbr].set(lanes, unique_indices=True)


def _expand_rows_dense(compact, row_ids, n: int):
    """Scatter (R, ...) compact reader-row draws into dense (n, ...) rows.

    ``row_ids`` are the plan's raw slot ids — dead (out-of-range) slots drop
    out of the scatter; rows not covered by a live slot stay False and are
    never consumed (non-reader rows are don't-care in every engine).
    """
    base = jnp.zeros((n,) + compact.shape[1:], compact.dtype)
    return base.at[row_ids].set(compact, mode="drop", unique_indices=True)


def _delivery_mask_dense(cfg: SimConfig, channel, k_mask, nbr):
    """The tick's dense (N, n) write-delivery mask under the new schedule:
    a dense draw when gossip is dense, the expanded K-lane draw under
    fanout.  Callers must have checked `_needs_delivery_mask`."""
    n = cfg.n_nodes
    if nbr is None:
        return _loss_mask(cfg, channel, k_mask, (n, n))
    lanes = _loss_mask(cfg, channel, k_mask, (n, cfg.workload.fanout))
    return _expand_lanes_dense(lanes, nbr, n)


def _response_mask_compact(cfg: SimConfig, channel, k_resp, slot_nid, nbr):
    """The tick's response-loss draw over reader-compaction rows.

    Returns (R, n) dense-columns when gossip is dense, else (R, K) neighbor
    lanes (lane j = responder ``nbr[slot_nid, j]``).  None when loss is off.
    """
    if cfg.loss_model == "none":
        return None
    r = slot_nid.shape[0]
    cols = cfg.n_nodes if nbr is None else cfg.workload.fanout
    return _loss_mask(cfg, channel, k_resp, (r, cols), receivers=slot_nid)


def _response_mask_dense(cfg: SimConfig, channel, plan, nbr):
    """Dense (n, n) [reader, responder] response mask for the per-pass
    engines: the compact draw expanded by scatter, with the fanout
    neighborhood restriction baked in (non-neighbor responders False).
    Under fanout with loss off this is the pure neighborhood mask.  None
    means "apply no mask" (dense, loss off)."""
    n = cfg.n_nodes
    compact = _response_mask_compact(cfg, channel, plan.k_resp, plan.slot_nid, nbr)
    if nbr is None:
        if compact is None:
            return None
        return _expand_rows_dense(compact, plan.slot_id, n)
    if compact is None:
        lanes = jnp.ones((plan.slot_nid.shape[0], cfg.workload.fanout), bool)
    else:
        lanes = compact
    dense_lanes = _expand_lanes_dense(lanes, nbr[plan.slot_nid], n)  # (R, n)
    return _expand_rows_dense(dense_lanes, plan.slot_id, n)


def _resolve_backstop(queue: wb.WriteQueue, store: bs.StoreState,
                      healthy, need_store, enq_idx):
    """Route fog-missed reads to the writer's ring or the backing store.

    Shared by both engines so the fault-tolerance semantics (§VI) cannot
    drift between them:
      * ``queue_hit`` — forwarded from the writer's ring: always for rows
        still PENDING (enqueued, not yet drained); while the store is down
        also for drained rows still physically resident in the ring;
      * ``store_read`` — a real synchronous store transaction (healthy only);
      * ``failed`` — store down and the row is not forwardable: the read
        fails outright (no transaction, still a miss).
    Row→ring-slot mapping uses the FIFO enqueue index; exact while nothing
    was dropped on overflow (the headline regime — see module docstring).
    """
    in_pending = (enq_idx >= queue.head) & (enq_idx < queue.tail)
    in_ring = (enq_idx >= queue.tail - queue.capacity) & (enq_idx < queue.tail)
    queue_hit = need_store & (in_pending | (~healthy & in_ring))
    store_read = need_store & ~queue_hit & healthy
    failed = need_store & ~queue_hit & ~healthy
    in_store = enq_idx < store.drained_total
    found = store_read & in_store
    return queue_hit, store_read, failed, found, in_store


def _resolve_backstop_keyed(queue: wb.WriteQueue, store: bs.StoreState,
                            healthy, need_store, key_ids):
    """Keyed-durability counterpart of ``_resolve_backstop`` (§VI semantics
    preserved) for mutable workloads, where a key's durable state is a
    VERSION, not a FIFO index.

    The writer's slot map gives the monotone enqueue index of each key's most
    recent ring entry; pending entries forward always, drained-but-resident
    entries forward only while the store is down, and a real store read
    consults the keyed membership table.  Returns
    (queue_hit, store_read, failed, found, served_ts) — ``served_ts`` is the
    data timestamp of the version actually served (-1 when nothing was).
    """
    ku = queue.key_universe
    kid = jnp.clip(jnp.asarray(key_ids, jnp.int32), 0, ku - 1)
    slot = queue.slot_of_key[kid]                 # monotone enqueue idx or -1
    in_pending = (slot >= queue.head) & (slot < queue.tail)
    in_ring = (slot >= 0) & (slot >= queue.tail - queue.capacity) & (slot < queue.tail)
    queue_hit = need_store & (in_pending | (~healthy & in_ring))
    store_read = need_store & ~queue_hit & healthy
    failed = need_store & ~queue_hit & ~healthy
    durable_ts = store.table_ts[kid]
    found = store_read & (durable_ts >= 0)
    ring_ts = queue.data_ts[jnp.maximum(slot, 0) % queue.capacity]
    served_ts = jnp.where(queue_hit, ring_ts, jnp.where(found, durable_ts, -1))
    return queue_hit, store_read, failed, found, served_ts


# --------------------------------------------------------------------------
# Broadcast-merge under the two insert policies.
# --------------------------------------------------------------------------

def _insert_own_rows(caches: CacheState, rows: CacheLine, now) -> CacheState:
    """Each node inserts its own generated row (origin-resident payload).

    Reference-engine / distributed-runtime form; the fused engine uses the
    batched ``insert_rows`` primitive instead.
    """
    from repro.core.flic import insert

    def per_node(cache, line):
        cache, _ev = insert(cache, line, now)
        return cache

    return jax.vmap(per_node)(caches, rows)


def _merge_replicate(
    caches: CacheState, rows: CacheLine, delivered: jax.Array, now,
    node_ids: jax.Array | None = None,
) -> CacheState:
    from repro.core.coherence import merge_broadcasts

    caches, _ev = merge_broadcasts(caches, rows, delivered, now, node_ids=node_ids)
    return caches


# --------------------------------------------------------------------------
# The fused fog probe.
# --------------------------------------------------------------------------

def _probe_all_caches(cfg: SimConfig, caches: CacheState, keys_q, sidx_q):
    """Probe R query keys against every node cache in one pass.

    Returns (hit (C,R), way (C,R), ts (C,R; -1 on miss), payload source) —
    ``payload source`` is a callable (best_c, slot) -> (R, D) so the inline
    backend can defer the payload gather to the winners only, while the
    kernel backends (which already computed per-responder payloads inside
    the kernel) just index them.
    """
    backend = cfg.probe_backend
    if backend in (None, "fused"):
        tags_cq = caches.tags[:, sidx_q]                    # (C, R, W)
        valid_cq = caches.valid[:, sidx_q]
        match = valid_cq & (tags_cq == keys_q[None, :, None])
        hit = jnp.any(match, axis=-1)                       # (C, R)
        way = jnp.argmax(match, axis=-1).astype(jnp.int32)  # first-way wins
        ts_cq = jnp.take_along_axis(
            caches.data_ts[:, sidx_q], way[..., None], axis=-1
        )[..., 0]
        ts = jnp.where(hit, ts_cq, -1)

        def payload(best_c, slot):
            return caches.data[best_c, sidx_q, way[best_c, slot]]

        return hit, way, ts, payload

    from repro.kernels import ops

    r = keys_q.shape[0]
    pad = (-r) % ops.FLIC_LOOKUP_BLOCK if r > ops.FLIC_LOOKUP_BLOCK else 0
    kq = jnp.concatenate([keys_q, jnp.full((pad,), NULL_TAG)]) if pad else keys_q
    sq = jnp.concatenate([sidx_q, jnp.zeros((pad,), jnp.int32)]) if pad else sidx_q

    def one_cache(tags, data_ts, valid, data):
        return ops.flic_lookup(
            tags, data_ts, valid, data,
            kq.astype(jnp.int32), sq, backend=backend,
        )

    hit, ts, pay, way = jax.vmap(one_cache)(
        caches.tags.astype(jnp.int32), caches.data_ts,
        caches.valid, caches.data,
    )
    if pad:
        hit, ts, pay, way = hit[:, :r], ts[:, :r], pay[:, :r], way[:, :r]

    def payload(best_c, slot):
        return pay[best_c, slot]

    return hit, way, ts, payload


# --------------------------------------------------------------------------
# One tick (fused engine).
#
# Each section runs under a named stage (plan, sweep, upsert, probe,
# writer): the scope lands in the ``op_name`` metadata of every HLO op it
# lowers to, so a device trace of the scan can be reduced per stage.  It is
# metadata only: compiled without it, the program has the same instructions
# (a few carry other numbers in their names).  Section 6's scalar
# accounting stays outside every stage.
# --------------------------------------------------------------------------

def _stage(name: str):
    return jax.named_scope(f"stage.{name}")


def sim_tick(cfg: SimConfig, state: SimState, _=None) -> tuple[SimState, TickMetrics]:
    n = cfg.n_nodes
    spec = cfg.workload
    t = state.tick
    with _stage("plan"):
        # The plan stage: ALL request generation (writes, reads, masks,
        # slots, the tick's PRNG split) happens in workload.plan_tick; this
        # engine only executes the returned tensors.
        plan = wl.plan_tick(cfg, state.plan, t, state.rng)
        m = TickMetrics.zeros()
        caches = _node_minor(state.caches)
        latest_ts = state.latest_ts
        store_in = state.store
        if cfg.outage_schedule:
            store_in = bs.apply_outage_schedule(store_in, t, cfg.outage_schedule)

        # ---- 0. churn: rejoining nodes cold-start -------------------------
        online = plan.online
        if spec.has_churn:
            caches = invalidate_nodes(caches, plan.rejoin)
            n_rejoin = jnp.sum(plan.rejoin.astype(jnp.int32))
        else:
            n_rejoin = jnp.int32(0)

        # ---- 1. materialize the plan's write waves ------------------------
        rows_waves = [
            wl.plan_write_rows(cfg, plan, p, t) for p in range(spec.plan_waves)
        ]
        n_writes = jnp.sum(plan.w_valid.astype(jnp.int32))
    m = dataclasses.replace(m, writes_gen=n_writes)

    # ---- 2. fog broadcast under the loss model ----------------------------
    # New schedule (DESIGN.md §9): the channel advances once; the delivery
    # mask is drawn only when the sweep/merge consumes it, K-compact under
    # fanout.
    nbr = _neighbor_index(cfg)
    with _stage("plan"):
        channel, k_dmask = _advance_channel(cfg, state.channel, plan.k_deliver)
    with _stage("sweep"):
        if _needs_delivery_mask(cfg):
            delivered = _delivery_mask_dense(cfg, channel, k_dmask, nbr)
            if spec.has_churn:
                delivered = delivered & online[:, None]  # offline nodes hear nothing
        else:
            delivered = None  # write-once directory: provably unused
    n_coh = jnp.int32(0)
    if cfg.insert_policy == "directory":
        for rows in rows_waves:
            # Origin-resident payload via ONE batched upsert per wave.
            with _stage("upsert"):
                caches, _ev = insert_rows(caches, rows, t, backend=cfg.probe_backend)
            if spec.mutable:
                # The scenario can re-write keys: run the LIVE batched
                # coherence sweep (hearers update resident older copies in
                # place).  The sweep dispatches through the same
                # kernel-backend knob as the fog probe (inline winr
                # election, or kernels.ops.flic_update).
                with _stage("sweep"):
                    caches, n_coh_p = update_rows(
                        caches, rows, delivered, t, backend=cfg.probe_backend
                    )
                n_coh = n_coh + n_coh_p
            # else: write-once keys — the sweep is a provable no-op and is
            # skipped (see flic.update_rows; equivalence is asserted against
            # the reference engine which still runs it).
    else:
        for rows in rows_waves:
            with _stage("upsert"):
                caches = _merge_replicate(caches, rows, delivered, t)
    lan = n_writes.astype(jnp.float32) * cfg.row_bytes  # broadcasts on the medium

    # ---- 3. write-behind enqueue (single writer, §I.A.b) ------------------
    queue = state.queue
    with _stage("writer"):
        if spec.mutable:
            for p, rows in enumerate(rows_waves):
                queue, _acc = wb.enqueue_keyed(
                    queue, plan.w_kids[p], rows.data_ts, rows.origin, plan.w_valid[p]
                )
                latest_ts = latest_ts.at[
                    jnp.where(plan.w_valid[p], plan.w_kids[p], spec.key_universe)
                ].max(rows.data_ts, mode="drop")
        else:
            rows = rows_waves[0]
            queue, _acc = wb.enqueue(
                queue, rows.key, rows.data_ts, rows.origin, plan.w_valid[0]
            )

    # ---- 4. reads: execute the plan's read lanes --------------------------
    reading = plan.reading
    r_keys = plan.r_keys
    with _stage("probe"):
        # Reader compaction: the plan's (R,) slot tensors (for the staggered
        # schedule, the arithmetic progression node ≡ -t (mod read_period) with
        # static R = ceil(N / read_period); for trace replay, R = N).  The
        # fused probe touches (C, R, W) instead of the seed's (C, N, W).
        r_slots = plan.slot_ok.shape[0]
        r_ids = plan.slot_id                                           # (R,)
        slot_ok = plan.slot_ok
        r_gidx = plan.slot_nid                                         # safe gather
        keys_q = r_keys[r_gidx]
        sidx_q = (keys_q % jnp.uint32(cfg.cache_sets)).astype(jnp.int32)

        slots = jnp.arange(r_slots)
        if nbr is None:
            # 4a+4b fused (dense): ONE probe of the R queries against all C
            # caches serves the reader's local check (its own lane), the fog
            # broadcast query, and the LRU-touch scatter.
            hit_cq, way_cq, ts_cq, payload_of = _probe_all_caches(
                cfg, caches, keys_q, sidx_q
            )

            hit_local_slot = hit_cq[r_gidx, slots] & slot_ok           # (R,)
            need_fog_slot = slot_ok & ~hit_local_slot
            ts_local_slot = ts_cq[r_gidx, slots]

            # Response loss: each responder's reply may be lost independently.
            # The draw covers only the R reader-compaction rows (DESIGN.md §9).
            hit_fog_cq = hit_cq
            resp_rq = _response_mask_compact(cfg, channel, plan.k_resp, r_gidx, nbr)
            if resp_rq is not None:
                hit_fog_cq = hit_fog_cq & resp_rq.T                    # (C, R)
            if spec.has_churn:
                hit_fog_cq = hit_fog_cq & online[:, None]              # silent offline
            hit_fog_cq = hit_fog_cq & need_fog_slot[None, :]
            ts_fog = jnp.where(hit_fog_cq, ts_cq, -1)

            best_c = jnp.argmax(ts_fog, axis=0)                    # (R,) ties → lowest node id
            fog_hit_slot = jnp.any(hit_fog_cq, axis=0)
            best_ts_slot = jnp.where(fog_hit_slot, ts_fog[best_c, slots], -1)
            best_payload_slot = payload_of(best_c, slots)              # (R, D)

            # LRU refresh in ONE scatter: the reader's local hit plus every
            # responder that served a query.  The scatter-max runs along the
            # SHARED query set-index vector (R slice-updates, each vectorized
            # over all C caches) with the per-cache way variability moved into
            # the VALUES — XLA serializes per-element (C, R)-indexed scatters
            # on CPU.
            touch_cq = hit_fog_cq.at[r_gidx, slots].max(hit_local_slot)
            touch_w = touch_cq[:, :, None] & (
                jax.lax.iota(jnp.int32, cfg.cache_ways)[None, None, :]
                == way_cq[:, :, None]
            )
            caches = dataclasses.replace(
                caches,
                last_use=caches.last_use.at[:, sidx_q].max(jnp.where(touch_w, t, -1)),
            )

            n_responses = jnp.sum(hit_fog_cq.astype(jnp.int32))
        else:
            # 4a+4b fused (fanout): the reader probes ONLY itself plus its K
            # ring neighbors — (R, K+1) lanes, lane 0 local — so the probe,
            # response loss, winner election, payload gather and LRU touch are
            # all O(R·K), never O(N²).  Ties break by lane (nearest ring
            # offset) instead of lowest node id: unobservable, because
            # same-(key, ts) payloads are value-identical by construction.
            cols = jnp.concatenate([r_gidx[:, None], nbr[r_gidx]], axis=1)
            sidx_n = (r_keys % jnp.uint32(cfg.cache_sets)).astype(jnp.int32)
            lanes = _ring_lanes(cfg, sidx_n)
            tags_l = lanes(caches.tags)[r_gidx]                        # (R, K+1, W)
            valid_l = lanes(caches.valid)[r_gidx]
            match_l = valid_l & (tags_l == keys_q[:, None, None])
            hit_l = jnp.any(match_l, axis=-1)                          # (R, K+1)
            way_l = jnp.argmax(match_l, axis=-1).astype(jnp.int32)     # first-way wins
            ts_raw_l = jnp.take_along_axis(
                lanes(caches.data_ts)[r_gidx], way_l[..., None], axis=-1
            )[..., 0]

            hit_local_slot = hit_l[:, 0] & slot_ok                     # (R,)
            need_fog_slot = slot_ok & ~hit_local_slot
            ts_local_slot = jnp.where(hit_l[:, 0], ts_raw_l[:, 0], -1)

            hit_fog_l = hit_l[:, 1:]                                   # (R, K)
            resp_l = _response_mask_compact(cfg, channel, plan.k_resp, r_gidx, nbr)
            if resp_l is not None:
                hit_fog_l = hit_fog_l & resp_l
            if spec.has_churn:
                hit_fog_l = hit_fog_l & online[cols[:, 1:]]            # silent offline
            hit_fog_l = hit_fog_l & need_fog_slot[:, None]
            ts_fog_l = jnp.where(hit_fog_l, ts_raw_l[:, 1:], -1)

            best_lane = jnp.argmax(ts_fog_l, axis=1)                   # (R,)
            fog_hit_slot = jnp.any(hit_fog_l, axis=1)
            best_ts_slot = jnp.where(fog_hit_slot, ts_fog_l[slots, best_lane], -1)
            best_payload_slot = caches.data[
                cols[slots, 1 + best_lane], sidx_q, way_l[slots, 1 + best_lane]
            ]                                                          # (R, D)

            # LRU refresh: flat scatter-max over the touched (cache, set, way)
            # cells — O(R·K) updates, duplicates merge under max.
            touch_l = jnp.concatenate([hit_local_slot[:, None], hit_fog_l], axis=1)
            flat = (cols * cfg.cache_sets + sidx_q[:, None]) * cfg.cache_ways + way_l
            oob = n * cfg.cache_sets * cfg.cache_ways
            flat = jnp.where(touch_l, flat, oob)
            caches = dataclasses.replace(
                caches,
                last_use=caches.last_use.reshape(-1)
                .at[flat.reshape(-1)].max(t, mode="drop")
                .reshape(caches.last_use.shape),
            )

            n_responses = jnp.sum(hit_fog_l.astype(jnp.int32))

        n_fog_queries = jnp.sum(need_fog_slot.astype(jnp.int32))

    with _stage("writer"):
        # 4c. writer-buffer forwarding, then the backing store (§VI).
        healthy = bs.store_healthy(store_in, t)
        need_store_slot = need_fog_slot & ~fog_hit_slot
        if spec.mutable:
            kids_q = plan.r_kids[r_gidx]
            (queue_hit_slot, store_read_slot, failed_slot, found_slot,
             served_ts_slot) = _resolve_backstop_keyed(
                queue, store_in, healthy, need_store_slot, kids_q
            )
        else:
            enq_idx_slot = plan.r_enq_idx[r_gidx]
            queue_hit_slot, store_read_slot, failed_slot, found_slot, _ = _resolve_backstop(
                queue, store_in, healthy, need_store_slot, enq_idx_slot
            )
        n_store_reads = jnp.sum(store_read_slot.astype(jnp.int32))
        n_queue_hits = jnp.sum(queue_hit_slot.astype(jnp.int32))
        n_failed = jnp.sum(failed_slot.astype(jnp.int32))
        lan = (
            lan + n_fog_queries * cfg.query_bytes
            + (n_responses + n_queue_hits) * cfg.row_bytes
        )
        txn = cfg.store.read_txn_bytes(store_in.drained_total)
        wan_rx = n_store_reads.astype(jnp.float32) * txn
        store = dataclasses.replace(
            store_in, api_calls=store_in.api_calls + n_store_reads
        )

    with _stage("probe"):
        # 4d. fill the reader's local cache from fog/queue/store responses.
        # Payload lanes are derived only for the R reader slots (non-slot lanes
        # are valid=False in fill_lines, so their data is never read).
        fill_ok_slot = fog_hit_slot | queue_hit_slot | found_slot
        if spec.mutable:
            # Queue/store fills carry the VERSION actually served; payloads are
            # re-derived from (key, version) — identical to what the origin wrote.
            slot_payload = jnp.where(
                fog_hit_slot[:, None], best_payload_slot,
                wl.versioned_payload(keys_q, served_ts_slot, cfg.payload_dim),
            )
            fill_ts_slot = jnp.where(fog_hit_slot, best_ts_slot, served_ts_slot)
            fill_ts = jnp.full((n,), -1, jnp.int32).at[r_ids].set(
                fill_ts_slot, mode="drop"
            )
            fill_origin = jnp.full((n,), -1, jnp.int32)
        else:
            slot_payload = jnp.where(
                fog_hit_slot[:, None], best_payload_slot,
                _payload_for(keys_q, cfg.payload_dim),                 # (R, D)
            )
            fill_ts = plan.r_fill_ts.at[r_ids].set(
                jnp.where(fog_hit_slot, best_ts_slot, plan.r_fill_ts[r_gidx]),
                mode="drop",
            )
            fill_origin = plan.r_src
        fill_data = jnp.zeros((n, cfg.payload_dim), jnp.float32).at[r_ids].set(
            slot_payload, mode="drop"
        )
        fill_valid = jnp.zeros((n,), bool).at[r_ids].set(fill_ok_slot, mode="drop")
        fill_lines = CacheLine(
            key=r_keys,
            data_ts=fill_ts,
            origin=fill_origin,
            data=fill_data,
            valid=fill_valid,
            dirty=jnp.zeros((n,), bool),
        )
    with _stage("upsert"):
        caches, _ev = insert_rows(caches, fill_lines, t, backend=cfg.probe_backend)

    with _stage("probe"):
        # 4e. staleness: served reads whose version is older than the newest
        # write of that key (the soft-coherence lag the paper accepts, §I.A.a).
        if spec.mutable:
            served_slot = hit_local_slot | fog_hit_slot | queue_hit_slot | found_slot
            got_ts_slot = jnp.where(
                hit_local_slot, ts_local_slot,
                jnp.where(fog_hit_slot, best_ts_slot, served_ts_slot),
            )
            truth_slot = latest_ts[jnp.clip(kids_q, 0, spec.key_universe - 1)]
            n_stale = jnp.sum((served_slot & (got_ts_slot < truth_slot)).astype(jnp.int32))
        else:
            n_stale = jnp.int32(0)

    with _stage("writer"):
        # ---- 5. writer drain + store commit --------------------------------
        queue, n_drained, n_calls = wb.drain(
            queue, t, healthy,
            rate_per_tick=cfg.store.api_rate_per_tick,
            burst=cfg.store.api_burst,
            max_per_tick=cfg.writer_max_per_tick,
        )
        store = bs.commit_writes(store, n_drained, n_calls, plan.k_coll, cfg.store)
        if spec.mutable:
            d_kids, d_ts, d_live = wb.drained_entries(
                queue, n_drained, cfg.writer_max_per_tick
            )
            store = bs.commit_keyed_rows(store, d_kids, d_ts, d_live)
        wan_tx = cfg.store.write_txn_bytes(n_drained)

    # ---- 6. latency model + baseline accounting ----------------------------
    n_reads = jnp.sum(reading.astype(jnp.int32))
    n_hits_local = jnp.sum(hit_local_slot.astype(jnp.int32))
    n_fog_hits = jnp.sum(fog_hit_slot.astype(jnp.int32))
    lat = (
        n_hits_local.astype(jnp.float32) * cfg.lat_local
        + (n_fog_hits + n_queue_hits).astype(jnp.float32)
        * (cfg.lat_lan_base + cfg.lat_lan_per_node * n)
        + (n_store_reads + n_failed).astype(jnp.float32) * cfg.lat_store
    )
    # Baseline: no fog cache — every write and every read goes to the store.
    # The baseline table appends EVERY generated write (no coalescing), i.e.
    # all accepted + coalesced + dropped enqueues so far; on the default
    # stream this is exactly the old (t + 1) * n.
    baseline_table_rows = queue.tail + queue.dropped + queue.coalesced
    baseline = (
        n_writes.astype(jnp.float32) * cfg.row_bytes
        + n_reads.astype(jnp.float32) * cfg.store.read_txn_bytes(baseline_table_rows)
    )

    metrics = dataclasses.replace(
        m,
        wan_tx_bytes=wan_tx,
        wan_rx_bytes=wan_rx,
        lan_bytes=lan,
        reads=n_reads,
        hits_local=n_hits_local,
        hits_fog=n_fog_hits,
        hits_queue=n_queue_hits,
        misses=n_store_reads + n_failed,
        store_found=jnp.sum(found_slot.astype(jnp.int32)),
        store_missing=jnp.sum((store_read_slot & ~found_slot).astype(jnp.int32)),
        writes_drained=n_drained,
        queue_depth=queue.size(),
        queue_dropped=queue.dropped,
        store_txn_bytes=wan_rx + wan_tx,
        store_txns=n_store_reads + n_calls,
        read_latency_sum=lat,
        baseline_wan_bytes=baseline,
        coherence_updates=n_coh,
        stale_reads=n_stale,
        writes_coalesced=queue.coalesced - state.queue.coalesced,
        churn_rejoins=n_rejoin,
    )
    new_state = SimState(
        caches=_node_minor(caches), queue=queue, store=store, channel=channel,
        tick=t + 1, rng=plan.rng_next, latest_ts=latest_ts,
        plan=plan.state_next,
    )
    return new_state, metrics


# --------------------------------------------------------------------------
# The scan driver: engine selection, metrics thinning, buffer donation.
# --------------------------------------------------------------------------

def _tick_fn(engine: str):
    if engine == "reference":
        from repro.core.simulator_ref import sim_tick_ref

        return sim_tick_ref
    if engine != "fused":
        raise ValueError(f"unknown engine {engine!r}; use 'fused' or 'reference'")
    return sim_tick


@partial(jax.jit, static_argnums=(0, 1, 3, 4), donate_argnums=(2,))
def _run_scan(cfg: SimConfig, ticks: int, state: SimState,
              metrics_every: int, engine: str):
    tick = _tick_fn(engine)
    return windowed_scan(lambda s: tick(cfg, s), state, ticks, metrics_every)


def run_sim(
    cfg: SimConfig, ticks: int, seed: int = 0, *,
    engine: str = "fused", metrics_every: int = 1,
) -> tuple[SimState, TickMetrics]:
    """Run ``ticks`` simulation steps; returns (final_state, metric series).

    ``engine``: ``"fused"`` (default hot path) or ``"reference"`` (the
    retained pre-fusion pipeline — bit-identical metrics, used by the
    equivalence suite and as the benchmark baseline).

    ``metrics_every``: emit one aggregated metrics row per this many ticks
    (flows summed, gauges last) — thins the scanned stack ~k× for long runs
    without changing what ``summarize`` reports.  The scan carry is donated,
    so state buffers are reused in place across calls.
    """
    wl.validate_run(cfg, ticks)
    state = init_sim(dataclasses.replace(cfg, seed=seed))
    return _run_scan(cfg, ticks, state, metrics_every, engine)


def run_any_engine(
    cfg: SimConfig, ticks: int, seed: int = 0, *,
    engine: str = "fused", metrics_every: int = 1, axis: str = "data",
):
    """Engine-agnostic dispatcher for the conformance contract (DESIGN.md §8).

    ``engine`` is ``"reference"`` / ``"fused"`` (single-host ``run_sim``),
    ``"distributed"`` — the bit-identical parity ``shard_map`` runtime — or
    ``"sharded"`` — the bandwidth-lean engine #4 (consistent-hash routing,
    per-shard PRNG, tolerance-tier conformance; DESIGN.md §10).  Both mesh
    engines run on a 1-D mesh over ALL visible devices (``cfg.n_nodes``
    must divide the device count; force the count with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K``).

    Every engine returns ``(final_state, TickMetrics series)`` with the same
    series shape; ``tests/conformance.py`` asserts the series (and therefore
    the summarized metrics) are bit-identical across all three for every
    scenario × seed × outage schedule.  ``metrics_every`` thinning is
    supported by EVERY engine (the distributed scan aggregates the same
    fixed windows per shard) under the same constraint: ``ticks`` must be
    divisible by the window.
    """
    if metrics_every != 1 and ticks % metrics_every != 0:
        raise ValueError(
            f"metrics thinning aggregates fixed windows on every engine "
            f"(including distributed): ticks ({ticks}) must be divisible by "
            f"metrics_every ({metrics_every})"
        )
    if engine in ("distributed", "sharded"):
        ndev = len(jax.devices())
        mesh = jax.make_mesh(
            (ndev,), (axis,), axis_types=(jax.sharding.AxisType.Auto,)
        )
        if engine == "sharded":
            # Engine #4 (DESIGN.md §10): bandwidth-lean, tolerance-tier
            # conformance instead of bit-identity.
            from repro.core.sharded import run_sharded_sim

            return run_sharded_sim(
                mesh, cfg, ticks, axis=axis, seed=seed,
                metrics_every=metrics_every,
            )
        from repro.core.distributed import run_distributed_sim

        return run_distributed_sim(
            mesh, cfg, ticks, axis=axis, seed=seed, metrics_every=metrics_every
        )
    return run_sim(cfg, ticks, seed=seed, engine=engine, metrics_every=metrics_every)
