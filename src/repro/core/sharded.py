"""Engine #4, ``"sharded"``: the bandwidth-lean fog under ``shard_map``.

The parity engine (``distributed.fog_shard_tick``) buys bit-identity with
the single-host engines by evaluating every global singleton REPLICATED and
broadcasting the full dense read/merge tensors through collectives — its
per-tick wire cost grows with n and payload_dim regardless of live traffic.
This engine spends that bit-identity to keep traffic local, the paper's
actual headline claim (>50% fewer bytes on the wire):

  * **Per-shard PRNG streams.**  Each shard folds its rank into the seed and
    runs its own split schedule — no replicated global draws, so nothing has
    to agree bitwise and nothing global is broadcast.  The DETERMINISTIC
    plan quantities (the staggered read schedule, the rate/online/rejoin
    masks) are pure functions of (spec, t, node id) and still agree exactly;
    conformance (tests/conformance.py, tolerance tier) asserts exact
    equality of reads / writes_gen / churn_rejoins and global write
    conservation, with epsilon bounds on the ratio metrics.
  * **Consistent-hash key→node routing** (``workload.ring_candidates`` /
    ``route_keys``): every key has a home node — the first ONLINE candidate
    on a virtual-node hash ring — agreed by all shards with zero
    communication.  Writes are forwarded to the key's home shard (bounded
    ppermute buckets), which owns the key's writer-ring entry, durable
    commit and staleness ground truth; reads that miss locally route their
    query to the home shard instead of broadcasting fog-wide.
  * **Fan-out-bounded shard-local gossip.**  The coherence sweep runs only
    inside the shard (k = min(spec.fanout, n_local - 1) ring neighbors) —
    gossip never crosses shard boundaries.
  * **psum-only summaries.**  The single collective reduction per tick is
    one stacked (M,) f32 psum of scalar metric partials.

What crosses the wire per tick (all STATIC shapes, counted in
``TickMetrics.wire_bytes`` via the same ring-cost model as the parity
engine): (p-1) write-forward buckets of n_local rows x 5 B (key id + live
flag — timestamps are the tick, payloads are pure in (key, ts), so neither
ships), (p-1) read-query buckets of ceil(n_local/read_period) rows x 5 B,
the matching response buckets (served flag + version) and the (M,) psum.

Documented divergences from the bit-identical tick semantics (DESIGN.md
§10): independent per-shard workload draws (same marginal distributions),
gossip confined to the shard, fog read resolution confined to the reader's
shard plus the key's home shard, the store API budget partitioned across
the p shard writers, per-shard ``latest_ts`` as a lower bound of global
write truth (staleness of home-resolved reads is exact; locally served
reads may under-count cross-shard staleness), and no response-loss on the
routed (reliable WAN) query path.

Supported workloads: mutable zipf cadence specs under the directory insert
policy — the scenario family the routing ring is for.  Stream, trace and
poisson specs raise with pointers at the parity engine.
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
from repro.core.flic import insert_rows, invalidate_nodes, update_rows
from repro.core.metrics import TickMetrics, allreduce_bytes, windowed_scan
from repro.core.simulator import (
    SimConfig,
    _advance_channel,
    _expand_lanes_dense,
    _loss_mask,
    _resolve_backstop_keyed,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedFogState:
    """Per-shard state: NOTHING is replicated except the tick counter.

    Outside ``shard_map`` the per-shard leaves carry a leading (p,) axis
    sharded over the mesh; ``caches``/``channel`` are sharded over their
    node axis like the parity engine.
    """

    caches: CacheState       # (n_local, S, W, ...) — this shard's nodes
    queue: wb.WriteQueue     # this shard's writer ring (keys homed here)
    store: bs.StoreState     # this shard's store view (keyed table slice)
    channel: GilbertElliott  # (n_local,) GE receiver states
    tick: jax.Array          # replicated int32
    rng: jax.Array           # PER-SHARD key: fold_in(PRNGKey(seed), rank)
    latest_ts: jax.Array     # (K,) int32 — newest write ts this shard saw


def _ring_perm(p: int, offset: int) -> list[tuple[int, int]]:
    return [(i, (i + offset) % p) for i in range(p)]


def sharded_fog_tick(
    cfg: SimConfig, axis: str, state: ShardedFogState
) -> tuple[ShardedFogState, TickMetrics]:
    """One tick of the bandwidth-lean fog.  Runs inside shard_map over ``axis``.

    Returns the replicated global ``TickMetrics`` row (equal on every shard
    after the closing psum).
    """
    n_local = state.caches.tags.shape[0]
    n = cfg.n_nodes
    p = n // n_local
    rank = jax.lax.axis_index(axis)
    spec = cfg.workload
    t = state.tick
    node_ids = rank * n_local + jnp.arange(n_local, dtype=jnp.int32)
    t_full = jnp.full((n_local,), t, jnp.int32)
    caches = state.caches
    latest_ts = state.latest_ts
    store_in = state.store
    if cfg.outage_schedule:
        store_in = bs.apply_outage_schedule(store_in, t, cfg.outage_schedule)

    # Per-shard PRNG schedule: rank-folded seed, own split tree.  The draws
    # are intentionally NOT the single-host schedule — only deterministic
    # (PRNG-free) plan quantities must agree across engines.
    rng_next, k_write, k_read, k_chan, k_coll = jax.random.split(state.rng, 5)

    # ---- 0. deterministic membership + churn cold-start --------------------
    if spec.has_churn:
        online_l = wl.online_mask(spec, n, t, node_ids=node_ids)
        rejoin_l = wl.rejoin_mask(spec, n, t, node_ids=node_ids)
        caches = invalidate_nodes(caches, rejoin_l)
        n_rejoin_l = jnp.sum(rejoin_l.astype(jnp.int32))
    else:
        online_l = jnp.ones((n_local,), bool)
        n_rejoin_l = jnp.int32(0)
    rate_l = wl.rate_mask(spec, n, t, node_ids=node_ids)

    # ---- 1. writes: per-shard draws, same marginals as the plan stage ------
    k_wr = jax.random.fold_in(k_write, wl.WRITE_SALT)
    kids_w = wl.sample_key_ids(spec, k_wr, (n_local,))
    w_valid = rate_l & online_l           # deterministic: writes_gen is exact
    keys_w = wl.key_hash(kids_w)
    rows_l = CacheLine(
        key=keys_w,
        data_ts=t_full,
        origin=node_ids,
        data=wl.versioned_payload(keys_w, t_full, cfg.payload_dim),
        valid=w_valid,
        dirty=jnp.zeros((n_local,), bool),
    )
    caches, _ev = insert_rows(caches, rows_l, t, backend=cfg.probe_backend)
    n_writes_l = jnp.sum(w_valid.astype(jnp.int32))

    # ---- 2. shard-local fan-out-bounded gossip (never crosses shards) ------
    channel, k_mask = _advance_channel(cfg, state.channel, k_chan)
    n_coh_l = jnp.int32(0)
    if n_local > 1:
        k_g = n_local - 1 if spec.fanout is None else min(spec.fanout, n_local - 1)
        nbr_l = jnp.asarray(wl.neighbor_table(n_local, k_g))
        lanes = _loss_mask(
            cfg, channel, jax.random.fold_in(k_mask, 1), (n_local, k_g)
        )
        delivered = _expand_lanes_dense(lanes, nbr_l, n_local)
        delivered = delivered & online_l[:, None]   # offline hear nothing
        caches, n_coh_l = update_rows(
            caches, rows_l, delivered, t, node_ids=node_ids,
            backend=cfg.probe_backend,
        )

    # ---- 3. route writes to their home shard (bounded ppermute buckets) ----
    # Only (key id, live flag) ship: the write's timestamp IS the tick and
    # payloads are pure in (key, ts) — the same purity argument the parity
    # engine uses for its winner tie-break.
    home_w = wl.route_keys(spec, n, t, kids_w)            # (n_local,) global
    dest_w = ((home_w // n_local) - rank) % p             # relative shard hop
    c_w = n_local
    home_kids = [kids_w]
    home_live = [w_valid & (dest_w == 0)]
    for o in range(1, p):
        send = w_valid & (dest_w == o)
        slot = jnp.where(send, jnp.cumsum(send.astype(jnp.int32)) - 1, c_w)
        b_kid = jnp.zeros((c_w,), jnp.int32).at[slot].set(kids_w, mode="drop")
        b_live = jnp.zeros((c_w,), bool).at[slot].set(send, mode="drop")
        perm = _ring_perm(p, o)
        home_kids.append(jax.lax.ppermute(b_kid, axis, perm))
        home_live.append(jax.lax.ppermute(b_live, axis, perm))
    hk = jnp.concatenate(home_kids)                       # (B,) home batch
    hv = jnp.concatenate(home_live)

    # Home-side ownership: the writer-ring entry, the durable commit path
    # and the staleness ground truth for this key live at its home shard.
    h_home = wl.route_keys(spec, n, t, hk)                # recomputed, agreed
    h_ts = jnp.full(hk.shape, t, jnp.int32)
    queue, _acc = wb.enqueue_keyed(state.queue, hk, h_ts, h_home, hv)
    latest_ts = latest_ts.at[
        jnp.where(hv, hk, spec.key_universe)
    ].max(t, mode="drop")
    # ... and a lower-bound truth entry for this shard's own writes (their
    # home may be remote; see module docstring on staleness accounting).
    latest_ts = latest_ts.at[
        jnp.where(w_valid, kids_w, spec.key_universe)
    ].max(t, mode="drop")

    # Home-node cache insert: the payload is re-derived, so hot keys are
    # resident where reads will route.  Sequential scalar upserts (rows may
    # collide on a node).
    h_keys = wl.key_hash(hk)
    h_lines = CacheLine(
        key=h_keys,
        data_ts=h_ts,
        origin=jnp.full(hk.shape, -1, jnp.int32),
        data=wl.versioned_payload(h_keys, h_ts, cfg.payload_dim),
        valid=hv,
        dirty=jnp.zeros(hk.shape, bool),
    )
    h_idx = jnp.clip(h_home - rank * n_local, 0, n_local - 1)

    def _home_insert(c, x):
        line, i = x
        ci = jax.tree.map(lambda a: a[i], c)
        ci, _ = _insert(ci, line, t)
        return jax.tree.map(lambda a, b: a.at[i].set(b), c, ci), None

    caches, _ = jax.lax.scan(_home_insert, caches, (h_lines, h_idx))

    # ---- 4. reads: local probe -> shard-local fog -> the key's home --------
    # The staggered schedule is deterministic, so the global read count is
    # exact across engines.
    reading_l = ((t + node_ids) % cfg.read_period == 0) & (t > 0) & online_l
    r_kids = wl.sample_key_ids(spec, k_read, (n_local,))
    r_keys = wl.key_hash(r_kids)
    sidx = (r_keys % jnp.uint32(cfg.cache_sets)).astype(jnp.int32)

    def self_probe(cache: CacheState, key, sidx_, is_reading):
        match = cache.valid[sidx_] & (cache.tags[sidx_] == key)
        hit = jnp.any(match) & is_reading
        way = jnp.argmax(match)
        ts = jnp.where(hit, cache.data_ts[sidx_, way], -1)
        s = jnp.where(hit, sidx_, cache.num_sets)
        cache = dataclasses.replace(
            cache, last_use=cache.last_use.at[s, way].max(t, mode="drop")
        )
        return cache, hit, ts

    caches, hit_local_l, ts_local_l = jax.vmap(self_probe)(
        caches, r_keys, sidx, reading_l
    )
    need_fog_l = reading_l & ~hit_local_l

    # 4b. shard-local fog probe: n_local queries x n_local caches, response
    # loss drawn per (reader, responder) against the shard's channel.
    def probe_cache(cache: CacheState, keys_q, sidx_q):
        tags_q = cache.tags[sidx_q]
        match = cache.valid[sidx_q] & (tags_q == keys_q[:, None])
        hit = jnp.any(match, axis=1)
        way = jnp.argmax(match, axis=1)
        ts = jnp.where(hit, cache.data_ts[sidx_q, way], -1)
        return hit, way, ts, cache.data[sidx_q, way]

    hits_qc, way_qc, ts_qc, data_qc = jax.vmap(
        probe_cache, in_axes=(0, None, None)
    )(caches, r_keys, sidx)                                # (nl_c, nl_q, ...)
    if cfg.loss_model != "none":
        resp_rq = _loss_mask(
            cfg, channel, jax.random.fold_in(k_mask, 2), (n_local, n_local)
        )                                                  # rows = readers
        hits_qc = hits_qc & resp_rq.T
    hits_qc = hits_qc & online_l[:, None] & need_fog_l[None, :]
    ts_masked = jnp.where(hits_qc, ts_qc, -1)
    q_slots = jnp.arange(n_local)
    best_c = jnp.argmax(ts_masked, axis=0)
    fog_hit_l = jnp.any(hits_qc, axis=0)
    best_ts_l = jnp.where(fog_hit_l, ts_masked[best_c, q_slots], -1)
    best_data_l = data_qc[best_c, q_slots]

    def touch(cache: CacheState, hits_c, ways_c):
        s = jnp.where(hits_c, sidx, cache.num_sets)
        return dataclasses.replace(
            cache,
            last_use=cache.last_use.at[s, ways_c].max(
                jnp.full_like(s, t), mode="drop"
            ),
        )

    caches = jax.vmap(touch)(caches, hits_qc, way_qc)
    n_responses_l = jnp.sum(hits_qc.astype(jnp.int32))

    # 4c. route the remaining misses to each key's home shard.
    healthy = bs.store_healthy(store_in, t)
    need_home_l = need_fog_l & ~fog_hit_l
    home_r = wl.route_keys(spec, n, t, r_kids)
    rdest = ((home_r // n_local) - rank) % p
    truth_l = latest_ts[jnp.clip(r_kids, 0, spec.key_universe - 1)]

    # Home-is-here readers already probed every cache of the home shard:
    # straight to the writer-ring / store backstop (§VI semantics).
    need0 = need_home_l & (rdest == 0)
    qh0, sr0, fl0, fd0, sts0 = _resolve_backstop_keyed(
        queue, store_in, healthy, need0, r_kids
    )
    home_served_l = qh0 | fd0
    home_ts_l = sts0
    n_queue_hits_l = jnp.sum(qh0.astype(jnp.int32))
    n_store_reads_l = jnp.sum(sr0.astype(jnp.int32))
    n_failed_l = jnp.sum(fl0.astype(jnp.int32))
    n_found_l = jnp.sum(fd0.astype(jnp.int32))
    n_store_missing_l = jnp.sum((sr0 & ~fd0).astype(jnp.int32))
    n_stale_l = jnp.sum((home_served_l & (sts0 < truth_l)).astype(jnp.int32))
    n_fog_hits_l = jnp.sum(fog_hit_l.astype(jnp.int32))
    n_fog_queries_l = jnp.sum(need_fog_l.astype(jnp.int32))

    # Cross-shard routed queries: one bucket per ring offset, capacity =
    # the shard's static reader bound (at most ceil(n_local/read_period)
    # nodes of a contiguous id block read per tick).
    c_r = max(1, -(-n_local // cfg.read_period))
    for o in range(1, p):
        send = need_home_l & (rdest == o)
        slot = jnp.where(send, jnp.cumsum(send.astype(jnp.int32)) - 1, c_r)
        q_kid = jnp.zeros((c_r,), jnp.int32).at[slot].set(r_kids, mode="drop")
        q_live = jnp.zeros((c_r,), bool).at[slot].set(send, mode="drop")
        q_rdr = jnp.full((c_r,), n_local, jnp.int32).at[slot].set(
            q_slots.astype(jnp.int32), mode="drop"
        )
        n_fog_queries_l = n_fog_queries_l + jnp.sum(send.astype(jnp.int32))
        perm_f = _ring_perm(p, o)
        a_kid = jax.lax.ppermute(q_kid, axis, perm_f)
        a_live = jax.lax.ppermute(q_live, axis, perm_f)

        # Home side: probe every local cache for the arrived keys, then the
        # writer-ring / store backstop.  Store transactions, hit categories
        # and staleness (exact — the home owns this key's truth) are all
        # counted HERE; only (served, version) returns to the reader.
        a_keys = wl.key_hash(a_kid)
        a_sidx = (a_keys % jnp.uint32(cfg.cache_sets)).astype(jnp.int32)
        a_hits, _a_way, a_ts_qc, _a_data = jax.vmap(
            probe_cache, in_axes=(0, None, None)
        )(caches, a_keys, a_sidx)                          # (nl, c_r)
        a_hits = a_hits & online_l[:, None] & a_live[None, :]
        a_fog = jnp.any(a_hits, axis=0)
        a_fog_ts = jnp.max(jnp.where(a_hits, a_ts_qc, -1), axis=0)
        a_need = a_live & ~a_fog
        aqh, asr, afl, afd, asts = _resolve_backstop_keyed(
            queue, store_in, healthy, a_need, a_kid
        )
        a_served = a_fog | aqh | afd
        a_served_ts = jnp.where(a_fog, a_fog_ts, asts)
        a_truth = latest_ts[jnp.clip(a_kid, 0, spec.key_universe - 1)]
        n_fog_hits_l = n_fog_hits_l + jnp.sum(a_fog.astype(jnp.int32))
        n_responses_l = n_responses_l + jnp.sum(a_hits.astype(jnp.int32))
        n_queue_hits_l = n_queue_hits_l + jnp.sum(aqh.astype(jnp.int32))
        n_store_reads_l = n_store_reads_l + jnp.sum(asr.astype(jnp.int32))
        n_failed_l = n_failed_l + jnp.sum(afl.astype(jnp.int32))
        n_found_l = n_found_l + jnp.sum(afd.astype(jnp.int32))
        n_store_missing_l = n_store_missing_l + jnp.sum(
            (asr & ~afd).astype(jnp.int32)
        )
        n_stale_l = n_stale_l + jnp.sum(
            (a_served & (a_served_ts < a_truth)).astype(jnp.int32)
        )
        store_in = dataclasses.replace(
            store_in, api_calls=store_in.api_calls + jnp.sum(asr.astype(jnp.int32))
        )

        perm_b = _ring_perm(p, p - o)                      # inverse hop
        r_served = jax.lax.ppermute(a_served, axis, perm_b)
        r_ts = jax.lax.ppermute(a_served_ts, axis, perm_b)
        home_served_l = home_served_l.at[q_rdr].set(
            r_served & q_live, mode="drop"
        )
        home_ts_l = home_ts_l.at[q_rdr].set(r_ts, mode="drop")

    store = dataclasses.replace(
        store_in, api_calls=store_in.api_calls + jnp.sum(sr0.astype(jnp.int32))
    )
    txn = cfg.store.read_txn_bytes(store_in.drained_total)
    wan_rx_l = n_store_reads_l.astype(jnp.float32) * txn

    # 4d. fill the reader's cache from fog / home responses.
    served_l = fog_hit_l | home_served_l
    fill_ts = jnp.where(fog_hit_l, best_ts_l, home_ts_l)
    fill_lines = CacheLine(
        key=r_keys,
        data_ts=fill_ts,
        origin=jnp.full((n_local,), -1, jnp.int32),
        data=jnp.where(
            fog_hit_l[:, None], best_data_l,
            wl.versioned_payload(r_keys, fill_ts, cfg.payload_dim),
        ),
        valid=served_l,
        dirty=jnp.zeros((n_local,), bool),
    )

    def fill(cache, line):
        cache, _ = _insert(cache, line, t)
        return cache

    caches = jax.vmap(fill)(caches, fill_lines)

    # Staleness of locally served reads, against the shard's lower-bound
    # truth (home-resolved reads were judged exactly at their home above).
    got_ts_l = jnp.where(hit_local_l, ts_local_l, best_ts_l)
    n_stale_l = n_stale_l + jnp.sum(
        ((hit_local_l | fog_hit_l) & (got_ts_l < truth_l)).astype(jnp.int32)
    )

    # ---- 5. per-shard writer drain; the API budget is partitioned ----------
    queue, n_drained_l, n_calls_l = wb.drain(
        queue, t, healthy,
        rate_per_tick=cfg.store.api_rate_per_tick / p,
        burst=max(cfg.store.api_burst / p, 1.0),
        max_per_tick=cfg.writer_max_per_tick,
    )
    store = bs.commit_writes(store, n_drained_l, n_calls_l, k_coll, cfg.store)
    d_kids, d_ts, d_live = wb.drained_entries(
        queue, n_drained_l, cfg.writer_max_per_tick
    )
    store = bs.commit_keyed_rows(store, d_kids, d_ts, d_live)
    wan_tx_l = cfg.store.write_txn_bytes(n_drained_l)

    # ---- 6. ONE stacked psum of scalar partials; global expressions after --
    n_reads_l = jnp.sum(reading_l.astype(jnp.int32))
    n_hits_local_l = jnp.sum(hit_local_l.astype(jnp.int32))
    baseline_rows_l = queue.tail + queue.dropped + queue.coalesced
    baseline_l = (
        n_writes_l.astype(jnp.float32) * cfg.row_bytes
        + n_reads_l.astype(jnp.float32) * cfg.store.read_txn_bytes(baseline_rows_l)
    )
    partials = jnp.stack([
        n_rejoin_l.astype(jnp.float32),
        n_writes_l.astype(jnp.float32),
        n_coh_l.astype(jnp.float32),
        n_reads_l.astype(jnp.float32),
        n_hits_local_l.astype(jnp.float32),
        n_fog_hits_l.astype(jnp.float32),
        n_queue_hits_l.astype(jnp.float32),
        n_store_reads_l.astype(jnp.float32),
        n_failed_l.astype(jnp.float32),
        n_found_l.astype(jnp.float32),
        n_store_missing_l.astype(jnp.float32),
        n_drained_l.astype(jnp.float32),
        n_calls_l.astype(jnp.float32),
        n_stale_l.astype(jnp.float32),
        n_fog_queries_l.astype(jnp.float32),
        n_responses_l.astype(jnp.float32),
        (queue.coalesced - state.queue.coalesced).astype(jnp.float32),
        queue.size().astype(jnp.float32),
        queue.dropped.astype(jnp.float32),
        wan_tx_l,
        wan_rx_l,
        baseline_l,
    ])
    g = jax.lax.psum(partials, axis)
    (g_rejoin, g_writes, g_coh, g_reads, g_hits_local, g_fog_hits,
     g_queue_hits, g_store_reads, g_failed, g_found, g_store_missing,
     g_drained, g_calls, g_stale, g_fog_queries, g_responses, g_coalesced,
     g_depth, g_dropped, g_wan_tx, g_wan_rx, g_baseline) = tuple(g)

    lan = (
        g_writes * cfg.row_bytes
        + g_fog_queries * cfg.query_bytes
        + (g_responses + g_queue_hits) * cfg.row_bytes
    )
    lat = (
        g_hits_local * cfg.lat_local
        + (g_fog_hits + g_queue_hits)
        * (cfg.lat_lan_base + cfg.lat_lan_per_node * n)
        + (g_store_reads + g_failed) * cfg.lat_store
    )
    # The wire inventory is static: (p-1) bounded buckets each way plus the
    # single metrics psum (see module docstring for the per-row layouts).
    wire = (
        p * (p - 1) * c_w * 5          # write forwards: key id + live flag
        + p * (p - 1) * c_r * 5        # routed queries: key id + live flag
        + p * (p - 1) * c_r * 5        # responses: served flag + version
        + allreduce_bytes(p, partials.shape[0], 4)
    )
    metrics = dataclasses.replace(
        TickMetrics.zeros(),
        wan_tx_bytes=g_wan_tx,
        wan_rx_bytes=g_wan_rx,
        lan_bytes=lan,
        reads=g_reads.astype(jnp.int32),
        hits_local=g_hits_local.astype(jnp.int32),
        hits_fog=g_fog_hits.astype(jnp.int32),
        hits_queue=g_queue_hits.astype(jnp.int32),
        misses=(g_store_reads + g_failed).astype(jnp.int32),
        store_found=g_found.astype(jnp.int32),
        store_missing=g_store_missing.astype(jnp.int32),
        writes_gen=g_writes.astype(jnp.int32),
        writes_drained=g_drained.astype(jnp.int32),
        queue_depth=g_depth.astype(jnp.int32),
        queue_dropped=g_dropped.astype(jnp.int32),
        store_txn_bytes=g_wan_rx + g_wan_tx,
        store_txns=(g_store_reads + g_calls).astype(jnp.int32),
        read_latency_sum=lat,
        baseline_wan_bytes=g_baseline,
        coherence_updates=g_coh.astype(jnp.int32),
        stale_reads=g_stale.astype(jnp.int32),
        writes_coalesced=g_coalesced.astype(jnp.int32),
        churn_rejoins=g_rejoin.astype(jnp.int32),
        wire_bytes=jnp.float32(wire),
    )
    new_state = ShardedFogState(
        caches=caches, queue=queue, store=store, channel=channel,
        tick=t + 1, rng=rng_next, latest_ts=latest_ts,
    )
    return new_state, metrics


def validate_sharded(cfg: SimConfig) -> None:
    """Reject workloads outside the sharded engine's supported family."""
    spec = cfg.workload
    if not (spec.mutable and spec.popularity == "zipf"
            and spec.arrivals == "cadence"):
        raise ValueError(
            f"engine='sharded' supports mutable zipf-cadence workloads "
            f"(popularity='zipf', arrivals='cadence'); got "
            f"popularity={spec.popularity!r}, arrivals={spec.arrivals!r}. "
            f"The consistent-hash routing ring homes KEY IDS, which the "
            f"stream/trace/poisson request shapes don't provide per lane — "
            f"use engine='distributed' (bit-identical parity) for those."
        )
    if cfg.insert_policy != "directory":
        raise ValueError(
            "engine='sharded' supports insert_policy='directory' only: the "
            "replicate ablation broadcasts every payload fog-wide, which is "
            "exactly the traffic this engine exists to avoid — use "
            "engine='distributed' for the replicate ablation."
        )


def init_sharded_fog(cfg: SimConfig, p: int, seed: int = 0) -> ShardedFogState:
    """Host-side full-fog state with a leading (p,) axis on per-shard leaves."""
    ku = cfg.workload.key_universe

    def per_shard(tree):
        return jax.tree.map(lambda x: jnp.stack([x] * p), tree)

    return ShardedFogState(
        caches=empty_cache(
            cfg.cache_sets, cfg.cache_ways, cfg.payload_dim, jnp.float32,
            batch=(cfg.n_nodes,),
        ),
        queue=per_shard(wb.empty_queue(cfg.queue_capacity, key_universe=ku)),
        store=per_shard(bs.init_store(key_universe=ku)),
        channel=GilbertElliott.init(cfg.n_nodes),
        tick=jnp.int32(0),
        rng=jnp.stack([
            jax.random.fold_in(jax.random.PRNGKey(seed), r) for r in range(p)
        ]),
        latest_ts=jnp.full((p, ku), -1, jnp.int32),
    )


def run_sharded_sim(
    mesh: Mesh,
    cfg: SimConfig,
    ticks: int,
    axis: str = "data",
    seed: int = 0,
    metrics_every: int = 1,
):
    """Run the bandwidth-lean fog for ``ticks`` on ``mesh``.

    Returns (final ShardedFogState, replicated TickMetrics series).  The
    series is NOT bit-identical to the other engines — it satisfies the
    tolerance-tier contract (DESIGN.md §10): exact deterministic counts
    (reads, writes_gen, churn_rejoins), exact global write conservation,
    and epsilon-bounded ratio metrics.
    """
    validate_sharded(cfg)
    wl.validate_run(cfg, ticks)
    ndev = mesh.shape[axis]
    assert cfg.n_nodes % ndev == 0, "n_nodes must divide the fog axis"
    if ticks % metrics_every != 0:
        raise ValueError(
            f"sharded metrics thinning aggregates fixed windows: ticks "
            f"({ticks}) must be divisible by metrics_every ({metrics_every})"
        )

    state = init_sharded_fog(cfg, ndev, seed)
    shard_leading = P(axis)
    state_spec = ShardedFogState(
        caches=jax.tree.map(lambda _: P(axis), state.caches),
        queue=jax.tree.map(lambda _: shard_leading, state.queue),
        store=jax.tree.map(lambda _: shard_leading, state.store),
        channel=jax.tree.map(lambda _: P(axis), state.channel),
        tick=P(),
        rng=shard_leading,
        latest_ts=shard_leading,
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(state_spec,),
        out_specs=(state_spec, jax.tree.map(lambda _: P(), TickMetrics.zeros())),
        check_vma=False,
    )
    def tick_shard(st):
        local = ShardedFogState(
            caches=st.caches,
            queue=jax.tree.map(lambda x: x[0], st.queue),
            store=jax.tree.map(lambda x: x[0], st.store),
            channel=st.channel,
            tick=st.tick,
            rng=st.rng[0],
            latest_ts=st.latest_ts[0],
        )
        new, mets = sharded_fog_tick(cfg, axis, local)
        out = ShardedFogState(
            caches=new.caches,
            queue=jax.tree.map(lambda x: x[None], new.queue),
            store=jax.tree.map(lambda x: x[None], new.store),
            channel=new.channel,
            tick=new.tick,
            rng=new.rng[None],
            latest_ts=new.latest_ts[None],
        )
        return out, mets

    @partial(jax.jit, donate_argnums=(0,))
    def run(st):
        return windowed_scan(tick_shard, st, ticks, metrics_every)

    state = jax.device_put(
        state,
        jax.tree.map(lambda s: NamedSharding(mesh, s), state_spec,
                     is_leaf=lambda x: isinstance(x, P)),
    )
    final, series = run(state)
    return final, series
