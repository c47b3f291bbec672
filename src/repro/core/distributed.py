"""Pod-scale FLIC: the fog cache under ``shard_map`` — full §VI parity.

This is the production embodiment of the paper's protocol on a TPU mesh
(DESIGN.md §2, §8): fog *nodes* are sharded across a mesh axis (the "fog"
axis — at pod scale that is the ``data`` axis); the UDP broadcast becomes
collective communication along that axis; soft coherence and the loss model
are unchanged.

Conformance strategy (DESIGN.md §8): the distributed tick is a *sharded
evaluation of the reference tick*, not a reinterpretation of it.  Global
singletons — the PRNG stream (the exact ``jax.random.split(rng, 6)``
schedule of ``sim_tick``), the workload draws, the writer's ring, the
backing store, and every metric — are computed REPLICATED: each device runs
the identical deterministic update, the standard SPMD idiom that needs no
extra communication.  Only the per-node cache array is sharded; each device
slices its nodes' lanes out of the replicated global draws.  The payoff is
the repo's central correctness asset: ``tests/conformance.py`` asserts the
``TickMetrics`` series is BIT-IDENTICAL across reference / fused /
distributed for every scenario × seed × outage schedule.

Communication per tick (what the dry-run lowers):
  * 1× all_gather of per-node fog-miss flags     — the read-request broadcast;
  * 1× pmax of per-query max data timestamps     — the soft-coherence merge;
  * 1× pmax of responder ids at the winning ts   — unique-winner election;
  * 1× psum of the winners' payload rows         — the response payload;
  * scalar psums for the sharded metric terms.

The §VI fault-tolerance paths run in full here, through the SAME shared
helpers as the single-host engines: writer-ring forwarding of pending rows
(``_resolve_backstop`` / ``_resolve_backstop_keyed`` on the replicated
ring), health-gated synchronous store reads, keyed versioned commits
(``backing_store.commit_keyed_rows``), load-store-buffer coalescing
(``writeback.enqueue_keyed``) and deterministic churn rejoins with
cold-started shard caches.

The fog read resolves soft coherence across devices with a max-timestamp
reduction; the winner is made unique by a second reduction over responder
ids at the winning timestamp, so the payload psum is exact.  The tie-break
direction is unobservable: payloads are pure functions of (key, data_ts)
(``workload.versioned_payload``), so any responder at the winning timestamp
scatters identical bytes.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import backing_store as bs
from repro.core import workload as wl
from repro.core import writeback as wb
from repro.core.cache_state import CacheLine, CacheState, empty_cache
from repro.core.coherence import GilbertElliott
from repro.core.flic import insert as _insert
from repro.core.flic import invalidate_nodes, update_rows
from repro.core.metrics import (
    TickMetrics,
    allgather_bytes,
    allreduce_bytes,
    windowed_scan,
)
from repro.core.simulator import (
    SimConfig,
    _advance_channel,
    _delivery_mask_dense,
    _insert_own_rows,
    _merge_replicate,
    _needs_delivery_mask,
    _neighbor_index,
    _payload_for,
    _resolve_backstop,
    _resolve_backstop_keyed,
    _response_mask_dense,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FogShardState:
    """Per-device slice of the fog + replicated global state."""

    caches: CacheState       # (n_local, S, W, ...) — this device's nodes
    queue: wb.WriteQueue     # replicated
    store: bs.StoreState     # replicated
    channel: GilbertElliott  # replicated (GE loss-model receiver states)
    tick: jax.Array          # replicated int32
    rng: jax.Array           # replicated key — the SAME per-tick split
    #                          schedule as the single-host engines
    latest_ts: jax.Array     # replicated (K,) int32 — newest write per key id
    #                          (mutable workloads; staleness ground truth)
    plan: wl.PlanState       # replicated carried plan-stage state (the
    #                          cumulative-write ring index, DESIGN.md §7)


def init_fog_shard(cfg: SimConfig, n_local: int, seed: int = 0) -> FogShardState:
    ku = cfg.workload.key_universe if cfg.workload.mutable else 0
    return FogShardState(
        caches=empty_cache(
            cfg.cache_sets, cfg.cache_ways, cfg.payload_dim, jnp.float32,
            batch=(n_local,),
        ),
        queue=wb.empty_queue(cfg.queue_capacity, key_universe=ku),
        store=bs.init_store(key_universe=ku),
        channel=GilbertElliott.init(cfg.n_nodes),
        tick=jnp.int32(0),
        rng=jax.random.PRNGKey(seed),
        latest_ts=jnp.full((ku,), -1, jnp.int32),
        plan=wl.init_plan_state(cfg),
    )


def fog_shard_tick(
    cfg: SimConfig, axis: str, state: FogShardState
) -> tuple[FogShardState, TickMetrics]:
    """One tick of the distributed fog. Must run inside shard_map over ``axis``.

    Emits the bit-identical ``TickMetrics`` of ``sim_tick`` /
    ``sim_tick_ref`` (see module docstring): replicated global computation
    for the singletons, per-shard slices for the cache work, collective
    reductions only where results are genuinely sharded.
    """
    n_local = state.caches.tags.shape[0]
    n = cfg.n_nodes
    rank = jax.lax.axis_index(axis)
    spec = cfg.workload
    t = state.tick
    node_ids = rank * n_local + jnp.arange(n_local, dtype=jnp.int32)
    # The plan stage, evaluated REPLICATED (replicated rng + plan state →
    # identical plan on every device); the shard slices its lanes below.
    plan = wl.plan_tick(cfg, state.plan, t, state.rng)
    m = TickMetrics.zeros()
    caches = state.caches
    latest_ts = state.latest_ts
    store_in = state.store
    if cfg.outage_schedule:
        store_in = bs.apply_outage_schedule(store_in, t, cfg.outage_schedule)

    def my(xs):
        """This rank's node slice of a replicated leading-(n,) array."""
        return jax.lax.dynamic_slice_in_dim(xs, rank * n_local, n_local, 0)

    # ---- 0. churn: rejoining shard nodes cold-start ------------------------
    online = plan.online
    if spec.has_churn:
        caches = invalidate_nodes(caches, my(plan.rejoin))
        n_rejoin = jnp.sum(plan.rejoin.astype(jnp.int32))
        online_l = my(online)
    else:
        online_l = jnp.ones((n_local,), bool)
        n_rejoin = jnp.int32(0)

    # ---- 1. materialize the plan's write waves (replicated tensors) --------
    rows_waves = [
        wl.plan_write_rows(cfg, plan, p, t) for p in range(spec.plan_waves)
    ]
    n_writes = jnp.sum(plan.w_valid.astype(jnp.int32))
    m = dataclasses.replace(m, writes_gen=n_writes)

    # ---- 2. fog broadcast under the loss model; sharded cache merge --------
    # R-compact schedule (DESIGN.md §9), evaluated REPLICATED: one channel
    # advance per tick; the delivery mask is drawn (and expanded from K
    # lanes under fanout) only when the sweep/merge consumes it.
    nbr = _neighbor_index(cfg)
    channel, k_dmask = _advance_channel(cfg, state.channel, plan.k_deliver)
    if _needs_delivery_mask(cfg):
        delivered = _delivery_mask_dense(cfg, channel, k_dmask, nbr)
        if spec.has_churn:
            delivered = delivered & online[:, None]  # offline nodes hear nothing
    else:
        delivered = None  # write-once directory: provably unused
    if cfg.insert_policy == "directory":
        n_coh_l = jnp.int32(0)
        for rows in rows_waves:
            rows_local: CacheLine = jax.tree.map(my, rows)
            caches = _insert_own_rows(caches, rows_local, t)
            if spec.mutable:
                # LIVE coherence sweep: all n broadcast rows against this
                # shard's caches, delivery mask sliced to the local
                # receivers.  Same kernel-backend dispatch as the fused
                # engine (DESIGN.md §4).
                caches, n_coh_p = update_rows(
                    caches, rows, my(delivered), t, node_ids=node_ids,
                    backend=cfg.probe_backend,
                )
                n_coh_l = n_coh_l + n_coh_p
        if spec.mutable:
            n_coh = jax.lax.psum(n_coh_l, axis)
        else:
            n_coh = jnp.int32(0)   # write-once: provable no-op, skipped
    else:
        for rows in rows_waves:
            caches = _merge_replicate(
                caches, rows, my(delivered), t, node_ids=node_ids
            )
        n_coh = jnp.int32(0)
    lan = n_writes.astype(jnp.float32) * cfg.row_bytes

    # ---- 3. write-behind enqueue (replicated single writer) ----------------
    queue = state.queue
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

    # ---- 4. reads: replicated plan lanes, sharded probes -------------------
    reading = plan.reading
    r_keys = plan.r_keys

    # 4a. local probe of this shard's readers (reference-engine semantics).
    r_keys_l = my(r_keys)
    sidx_l = (r_keys_l % jnp.uint32(cfg.cache_sets)).astype(jnp.int32)

    def self_probe(cache: CacheState, key, sidx, is_reading):
        match = cache.valid[sidx] & (cache.tags[sidx] == key)
        hit = jnp.any(match) & is_reading
        way = jnp.argmax(match)
        ts = jnp.where(hit, cache.data_ts[sidx, way], -1)
        s = jnp.where(hit, sidx, cache.num_sets)
        cache = dataclasses.replace(
            cache, last_use=cache.last_use.at[s, way].max(t, mode="drop")
        )
        return cache, hit, ts

    caches, hit_local_l, ts_local_l = jax.vmap(self_probe)(
        caches, r_keys_l, sidx_l, my(reading)
    )
    need_fog_l = my(reading) & ~hit_local_l
    # The fog read-request broadcast: which of the n global queries are live.
    q_need = jax.lax.all_gather(need_fog_l, axis, tiled=True)          # (n,)

    # 4b. fog probe: all n queries against this shard's caches.
    sidx_q = (r_keys % jnp.uint32(cfg.cache_sets)).astype(jnp.int32)

    def probe_cache(cache: CacheState):
        tags_q = cache.tags[sidx_q]                                    # (n, W)
        match = cache.valid[sidx_q] & (tags_q == r_keys[:, None])
        hit = jnp.any(match, axis=1)
        way = jnp.argmax(match, axis=1)
        ts = jnp.where(hit, cache.data_ts[sidx_q, way], -1)
        return hit, way, ts, cache.data[sidx_q, way]

    hits_qc, way_qc, ts_qc, data_qc = jax.vmap(probe_cache)(caches)  # (nl, n, ..)
    resp_dense = _response_mask_dense(cfg, channel, plan, nbr)
    if resp_dense is not None:
        # Replicated (reader, responder) mask — the single-host engines'
        # exact R-compact PRNG consumption expanded dense (with the fanout
        # neighborhood baked in) — sliced to the local responders.
        hits_qc = hits_qc & my(jnp.transpose(resp_dense))             # (nl, n)
    if spec.has_churn:
        hits_qc = hits_qc & online_l[:, None]   # offline responders are silent
    hits_qc = hits_qc & q_need[None, :]

    # Soft-coherence resolve: max data_ts wins; the winner is made unique by
    # a responder-id reduction at the winning ts (payloads are pure in
    # (key, ts), so the direction of this tie-break is unobservable).
    ts_masked = jnp.where(hits_qc, ts_qc, -1)                          # (nl, n)
    win_ts = jax.lax.pmax(jnp.max(ts_masked, axis=0), axis)            # (n,)
    fog_hit_q = win_ts >= 0
    at_max = hits_qc & (ts_qc == win_ts[None, :])
    nid = jnp.where(at_max, node_ids[:, None], -1)
    win_node = jax.lax.pmax(jnp.max(nid, axis=0), axis)                # (n,)
    is_winner = at_max & (node_ids[:, None] == win_node[None, :])  # ≤1 True globally
    # A select-sum, not a one-hot matmul: a TPU matmul at default precision
    # rounds f32 payloads to bf16.
    win_data = jnp.sum(jnp.where(is_winner[:, :, None], data_qc, 0.0), axis=0)
    win_data = jax.lax.psum(win_data, axis)                            # (n, D)

    # Responder LRU refresh on this shard.
    def touch(cache: CacheState, hits_c, ways_c):
        s = jnp.where(hits_c, sidx_q, cache.num_sets)
        return dataclasses.replace(
            cache,
            last_use=cache.last_use.at[s, ways_c].max(
                jnp.full_like(s, t), mode="drop"
            ),
        )

    caches = jax.vmap(touch)(caches, hits_qc, way_qc)

    n_fog_queries = jnp.sum(q_need.astype(jnp.int32))
    n_responses = jax.lax.psum(jnp.sum(hits_qc.astype(jnp.int32)), axis)

    # 4c. §VI fault tolerance — writer-ring forwarding then the store, via
    # the SAME shared helpers as the single-host engines (the ring and store
    # are replicated, so every device resolves the full global query set).
    healthy = bs.store_healthy(store_in, t)
    need_store = q_need & ~fog_hit_q
    if spec.mutable:
        queue_hit, store_read, failed, found, served_ts = _resolve_backstop_keyed(
            queue, store_in, healthy, need_store, plan.r_kids
        )
    else:
        queue_hit, store_read, failed, found, _ = _resolve_backstop(
            queue, store_in, healthy, need_store, plan.r_enq_idx
        )
    n_store_reads = jnp.sum(store_read.astype(jnp.int32))
    n_queue_hits = jnp.sum(queue_hit.astype(jnp.int32))
    n_failed = jnp.sum(failed.astype(jnp.int32))
    lan = (
        lan + n_fog_queries * cfg.query_bytes
        + (n_responses + n_queue_hits) * cfg.row_bytes
    )
    txn = cfg.store.read_txn_bytes(store_in.drained_total)
    wan_rx = n_store_reads.astype(jnp.float32) * txn
    store = dataclasses.replace(
        store_in, api_calls=store_in.api_calls + n_store_reads
    )

    # 4d. fill this shard's readers from fog/queue/store responses.
    fog_hit_l = my(fog_hit_q)
    win_ts_l = my(win_ts)
    win_data_l = my(win_data)
    fill_ok_l = fog_hit_l | my(queue_hit) | my(found)
    if spec.mutable:
        served_ts_l = my(served_ts)
        fill_lines = CacheLine(
            key=r_keys_l,
            data_ts=jnp.where(fog_hit_l, win_ts_l, served_ts_l),
            origin=jnp.full((n_local,), -1, jnp.int32),
            data=jnp.where(
                fog_hit_l[:, None], win_data_l,
                wl.versioned_payload(r_keys_l, served_ts_l, cfg.payload_dim),
            ),
            valid=fill_ok_l,
            dirty=jnp.zeros((n_local,), bool),
        )
    else:
        fill_lines = CacheLine(
            key=r_keys_l,
            data_ts=jnp.where(fog_hit_l, win_ts_l, my(plan.r_fill_ts)),
            origin=my(plan.r_src),
            data=jnp.where(
                fog_hit_l[:, None], win_data_l,
                _payload_for(r_keys_l, cfg.payload_dim),
            ),
            valid=fill_ok_l,
            dirty=jnp.zeros((n_local,), bool),
        )

    def fill(cache, line):
        cache, _ = _insert(cache, line, t)
        return cache

    caches = jax.vmap(fill)(caches, fill_lines)

    # 4e. staleness (mutable only): served reads on THIS shard whose version
    # is older than the key's newest write, psum-reduced to the global count.
    if spec.mutable:
        served_l = hit_local_l | fog_hit_l | my(queue_hit) | my(found)
        got_ts_l = jnp.where(
            hit_local_l, ts_local_l,
            jnp.where(fog_hit_l, win_ts_l, served_ts_l),
        )
        truth_l = latest_ts[jnp.clip(my(plan.r_kids), 0, spec.key_universe - 1)]
        n_stale = jax.lax.psum(
            jnp.sum((served_l & (got_ts_l < truth_l)).astype(jnp.int32)), axis
        )
    else:
        n_stale = jnp.int32(0)

    # ---- 5. writer drain + store commit (replicated) -----------------------
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

    # ---- 6. metrics: the exact expressions of ``sim_tick`` -----------------
    n_reads = jnp.sum(reading.astype(jnp.int32))
    n_hits_local = jax.lax.psum(jnp.sum(hit_local_l.astype(jnp.int32)), axis)
    n_fog_hits = jnp.sum(fog_hit_q.astype(jnp.int32))
    lat = (
        n_hits_local.astype(jnp.float32) * cfg.lat_local
        + (n_fog_hits + n_queue_hits).astype(jnp.float32)
        * (cfg.lat_lan_base + cfg.lat_lan_per_node * n)
        + (n_store_reads + n_failed).astype(jnp.float32) * cfg.lat_store
    )
    baseline_table_rows = queue.tail + queue.dropped + queue.coalesced
    baseline = (
        n_writes.astype(jnp.float32) * cfg.row_bytes
        + n_reads.astype(jnp.float32) * cfg.store.read_txn_bytes(baseline_table_rows)
    )
    # On-wire byte accounting (embodiment observable, excluded from the
    # bit-identity contract): the parity tick's collective inventory is
    # STATIC — every tensor above is dense regardless of live traffic —
    # so its modeled ring cost is a compile-time constant per tick.
    p_shards = n // n_local
    wire = (
        allgather_bytes(p_shards, n_local, 1)        # q_need broadcast (bool)
        + allreduce_bytes(p_shards, n, 4)            # win_ts pmax (i32)
        + allreduce_bytes(p_shards, n, 4)            # win_node pmax (i32)
        + allreduce_bytes(p_shards, n * cfg.payload_dim, 4)  # win_data psum
        + allreduce_bytes(p_shards, 1, 4)            # n_responses psum
        + allreduce_bytes(p_shards, 1, 4)            # n_hits_local psum
    )
    if spec.mutable:
        wire += (
            allreduce_bytes(p_shards, 1, 4)          # n_coh psum
            + allreduce_bytes(p_shards, 1, 4)        # n_stale psum
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
        store_found=jnp.sum(found.astype(jnp.int32)),
        store_missing=jnp.sum((store_read & ~found).astype(jnp.int32)),
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
        wire_bytes=jnp.float32(wire),
    )
    new_state = FogShardState(
        caches=caches, queue=queue, store=store, channel=channel,
        tick=t + 1, rng=plan.rng_next, latest_ts=latest_ts,
        plan=plan.state_next,
    )
    return new_state, metrics


def run_distributed_sim(
    mesh: Mesh,
    cfg: SimConfig,
    ticks: int,
    axis: str = "data",
    seed: int = 0,
    metrics_every: int = 1,
):
    """Run the sharded fog for ``ticks`` on ``mesh`` (nodes over ``axis``).

    ``cfg.n_nodes`` must divide evenly over the axis.  Returns
    (final FogShardState, TickMetrics series) — the series is bit-identical
    to ``run_sim(cfg, ticks, seed=seed)`` on either single-host engine
    (the conformance contract, DESIGN.md §8).

    ``metrics_every`` thins the scanned metrics stack exactly like the
    single-host engines: a windowed inner scan folds ``metrics_every`` ticks
    into one aggregated row per shard (``metrics.accumulate`` — flows
    summed, gauges last), so only one row per window is stacked and
    replicated out of the mesh.  The per-tick collectives themselves are
    NOT deferred across the window: the float metric fields
    (``read_latency_sum``, ``lan_bytes``, ...) are per-tick expression
    trees over psum-reduced counts, and summing counts before the float
    expressions would break the bitwise conformance contract (§8).
    """
    ndev = mesh.shape[axis]
    assert cfg.n_nodes % ndev == 0, "n_nodes must divide the fog axis"
    wl.validate_run(cfg, ticks)
    if ticks % metrics_every != 0:
        # fail before device_put/compile; windowed_scan re-checks under jit
        raise ValueError(
            f"distributed metrics thinning aggregates fixed windows: ticks "
            f"({ticks}) must be divisible by metrics_every ({metrics_every})"
        )

    state = init_fog_shard(cfg, cfg.n_nodes, seed)  # host-side full fog
    # Shard caches over the axis; everything else replicated.
    cache_spec = jax.tree.map(lambda _: P(axis), state.caches)
    repl = P()
    state_spec = FogShardState(
        caches=cache_spec,
        queue=jax.tree.map(lambda _: repl, state.queue),
        store=jax.tree.map(lambda _: repl, state.store),
        channel=jax.tree.map(lambda _: repl, state.channel),
        tick=repl,
        rng=repl,
        latest_ts=repl,
        plan=jax.tree.map(lambda _: repl, state.plan),
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(state_spec,),
        out_specs=(state_spec, jax.tree.map(lambda _: repl, TickMetrics.zeros())),
        check_vma=False,
    )
    def tick_shard(st):
        return fog_shard_tick(cfg, axis, st)

    @partial(jax.jit, donate_argnums=(0,))
    def run(st):
        # ONE thinning definition shared with the single-host engines
        # (metrics.windowed_scan) — the windows cannot drift between
        # engines, which the bitwise conformance contract depends on (§8).
        return windowed_scan(tick_shard, st, ticks, metrics_every)

    state = jax.device_put(
        state, NamedSharding(mesh, P())
    )  # replicate, then reshard caches
    state = dataclasses.replace(
        state,
        caches=jax.device_put(state.caches, jax.tree.map(
            lambda s: NamedSharding(mesh, s), cache_spec)),
    )
    final, series = run(state)
    return final, series
