"""A plain reference of the fog cache's tick, in NumPy, one step at a time.

It follows the semantics that the configuration states (FLIC, Sec. II-III:
soft-coherent upserts, LRU eviction, K-neighbour gossip with i.i.d. loss,
a single write-behind writer with a token bucket, a store that holds the
first ``drained_total`` rows or, keyed, the newest drained version of each
key), written plainly and independently of the code under test.  It shares
only the seeded inputs with the program: the same PRNG stream
(``jax.random`` on the host CPU, from the run's seed), the same key hash and
payload derivation, and the same Zipf inverse CDF (evaluated on the default
backend, see ``_zipf_cdf``).

Scope: what the cells run and no more: cadence arrivals, steady rate, no
churn, no store outage, the directory insert policy, Bernoulli loss,
K-neighbour gossip, stream or Zipf keys, a Sheets-like store that loses no
write.  Anything else raises, so a cell the reference cannot judge cannot
pass.

``payload_dtype`` stores payload lanes, and sums the modelled float row
fields, in another precision; the correctness control runs the reference
with ``bfloat16`` in the program's place.
"""
from __future__ import annotations

import jax
import numpy as np

U32 = np.uint32
_M1, _M2, _GOLDEN = U32(0x85EBCA6B), U32(0xC2B2AE35), U32(0x9E3779B9)
KEY_SALT = 0x5A1FCA5E        # zipf key-id hash domain
WRITE_SALT = 0x57A9          # write-key draw, folded into the tick's loss key
NULL_TAG = 0xFFFFFFFF
INT32_MAX = np.iinfo(np.int32).max

INT_FIELDS = ("reads", "hits_local", "hits_fog", "misses", "store_found",
              "store_missing", "writes_gen", "writes_drained", "queue_depth",
              "queue_dropped", "store_txns", "hits_queue", "ticks",
              "coherence_updates", "stale_reads", "writes_coalesced",
              "churn_rejoins")
FLOAT_FIELDS = ("wan_tx_bytes", "wan_rx_bytes", "lan_bytes", "store_txn_bytes",
                "read_latency_sum", "baseline_wan_bytes", "wire_bytes")
GAUGES = ("queue_depth", "queue_dropped")


def splitmix32(x):
    with np.errstate(over="ignore"):
        x = np.asarray(x, U32) + _GOLDEN
        x = (x ^ (x >> U32(16))) * _M1
        x = (x ^ (x >> U32(13))) * _M2
        return x ^ (x >> U32(16))


def hash2(a, b):
    a = np.asarray(a).astype(U32)
    b = np.asarray(b).astype(U32)
    with np.errstate(over="ignore"):
        return splitmix32(splitmix32(a) ^ (b + _GOLDEN + (a << U32(6)) + (a >> U32(2))))


def payload(keys, dim: int):
    """Lanes ~ U[0, 1) from the key hash: hash2(key, lane) / 2**32 in f32."""
    lanes = hash2(np.asarray(keys, U32)[..., None], np.arange(dim, dtype=U32))
    return lanes.astype(np.float32) / np.float32(2**32)


def versioned_payload(keys, ts, dim: int):
    return payload(hash2(keys, np.asarray(ts).astype(np.int32).astype(U32)), dim)


def key_of_id(kids):
    return hash2(np.asarray(kids).astype(U32), U32(KEY_SALT))


def neighbours(n: int, k: int):
    offs = np.asarray([(j // 2 + 1) * (1 if j % 2 == 0 else -1) for j in range(k)])
    return (np.arange(n)[:, None] + offs[None, :]) % n


def _f32(x):
    return np.float32(x)


class ReferenceSim:
    """The whole fog's state as NumPy arrays, advanced one tick at a time."""

    def __init__(self, spec: dict, seed: int, payload_dtype=np.float32):
        sim, store, wl = spec["sim"], spec["store"], spec["workload"]
        self._check_scope(sim, store, wl)
        self.n = n = int(sim["n_nodes"])
        self.ways = w = int(sim.get("cache_ways", 4))
        self.sets = s = int(sim["cache_lines"]) // w
        self.dim = d = int(sim.get("payload_dim", 8))
        self.row_bytes = int(sim.get("row_bytes", 148))
        self.query_bytes = int(sim.get("query_bytes", 32))
        self.period = int(sim.get("read_period", 15))
        self.window_ticks = max(1, round(int(sim.get("read_window_keys", 2000)) / n))
        self.loss_prob = float(sim.get("loss_prob", 0.02))
        self.qcap = int(sim.get("queue_capacity", 8192))
        self.max_drain = int(sim.get("writer_max_per_tick", 64))
        self.lat = (float(sim.get("lat_local", 1e-4)),
                    float(sim.get("lat_lan_base", 2e-3))
                    + float(sim.get("lat_lan_per_node", 1.2e-4)) * n,
                    float(sim.get("lat_store", 1.1)))
        self.store_row_bytes = int(store.get("row_bytes", 148))
        self.rate = float(store.get("api_rate_per_tick", 5.0))
        self.burst = float(store.get("api_burst", 100.0))
        self.zipf = wl.get("popularity", "stream") == "zipf"
        self.ku = int(wl.get("key_universe", 4096)) if self.zipf else 0
        self.alpha = float(wl.get("zipf_alpha", 0.9))
        self.k = int(wl["fanout"])
        self.nbr = neighbours(n, self.k)
        self.readers = -(-n // self.period)
        self.payload_dtype = payload_dtype

        shp = (n, s, w)
        self.tags = np.full(shp, NULL_TAG, U32)
        self.data_ts = np.full(shp, -1, np.int32)
        self.ins_ts = np.full(shp, -1, np.int32)
        self.origin = np.full(shp, -1, np.int32)
        self.valid = np.zeros(shp, bool)
        self.dirty = np.zeros(shp, bool)
        self.last_use = np.full(shp, -1, np.int32)
        self.data = np.zeros(shp + (d,), payload_dtype)
        self.q_keys = np.zeros(self.qcap, U32)
        self.q_ts = np.zeros(self.qcap, np.int32)
        self.q_origin = np.zeros(self.qcap, np.int32)
        self.head = self.tail = self.dropped = 0
        self.tokens = np.float32(0.0)
        self.slot_of_key = np.full(self.ku, -1, np.int64)
        self.coalesced = 0
        self.drained_total = self.api_calls = 0
        self.table_ts = np.full(self.ku, -1, np.int32)
        self.latest_ts = np.full(self.ku, -1, np.int32)
        self.cum_writes = 0
        self.t = 0
        self.evictions = 0           # upserts that displaced a valid LRU line

        self._cpu = jax.devices("cpu")[0]
        with jax.default_device(self._cpu):
            self.rng = jax.random.PRNGKey(seed)
        self.cdf = self._zipf_cdf() if self.zipf else None

    @staticmethod
    def _check_scope(sim, store, wl):
        unsupported = []
        if sim.get("insert_policy", "directory") != "directory":
            unsupported.append("insert_policy")
        if sim.get("loss_model", "bernoulli") != "bernoulli":
            unsupported.append("loss_model")
        if sim.get("outage_schedule"):
            unsupported.append("outage_schedule")
        if store.get("kind", "sheets") != "sheets":
            unsupported.append("store.kind")
        if float(store.get("collision_prob", 0.0)):
            unsupported.append("store.collision_prob")
        if sim.get("probe_backend") not in (None, "fused"):
            unsupported.append("probe_backend")
        if wl.get("popularity", "stream") not in ("stream", "zipf"):
            unsupported.append("popularity")
        if wl.get("arrivals", "cadence") != "cadence":
            unsupported.append("arrivals")
        if wl.get("rate", "steady") != "steady":
            unsupported.append("rate")
        if int(wl.get("churn_period", 0)):
            unsupported.append("churn_period")
        if wl.get("fanout") is None:
            unsupported.append("fanout=None")
        if unsupported:
            raise NotImplementedError(
                f"the plain reference does not model {unsupported}")

    def _zipf_cdf(self):
        """The truncated Zipf(alpha) CDF over the key ids, in float32, as
        the configuration's workload defines it (cumulative weights over
        their sum).  It is the one input evaluated on the default backend,
        the program's: XLA:TPU rounds this float32 power and cumulative sum
        differently from XLA:CPU (999 of 1000 entries differ by 2-7 ulps on
        a TPU v5e), which moves a few of the 2*10^4 key draws of a tick."""
        import jax.numpy as jnp

        @jax.jit
        def cdf():
            ranks = jnp.arange(1, self.ku + 1, dtype=jnp.float32)
            w = ranks ** jnp.float32(-self.alpha)
            return jnp.cumsum(w) / jnp.sum(w)

        return np.asarray(cdf())

    # ---- seeded inputs ----------------------------------------------------

    def _draws(self):
        """The tick's PRNG keys: (next, loss, age, src, response, store
        collision); the last is drawn, as the program draws it, but unused."""
        with jax.default_device(self._cpu):
            return list(jax.random.split(self.rng, 6))

    def _uniform(self, key, shape):
        with jax.default_device(self._cpu):
            return np.asarray(jax.random.uniform(key, shape))

    def _randint(self, key, shape, hi):
        with jax.default_device(self._cpu):
            return np.asarray(jax.random.randint(key, shape, 0, hi, dtype=np.int32))

    def _key_ids(self, key, shape):
        ids = np.searchsorted(self.cdf, self._uniform(key, shape), side="left")
        return np.clip(ids, 0, self.ku - 1).astype(np.int32)

    def _delivered(self, key, shape):
        return self._uniform(key, shape) >= np.float32(self.loss_prob)

    # ---- cache operations ---------------------------------------------------

    def _insert(self, nodes, keys, ts, origin, data, ok, now):
        """Soft-coherent upsert of one line into each listed node's cache:
        present -> overwrite only if strictly newer; else the first invalid
        way, else the least recently used one (first on ties)."""
        s = (keys % U32(self.sets)).astype(np.int64)
        tg, vl = self.tags[nodes, s], self.valid[nodes, s]
        use = np.where(vl, self.last_use[nodes, s], INT32_MAX)
        match = vl & (tg == keys[:, None])
        present = match.any(1)
        victim = np.where((~vl).any(1), np.argmax(~vl, 1), np.argmin(use, 1))
        way = np.where(present, np.argmax(match, 1), victim)
        stale = present & (ts <= self.data_ts[nodes, s, way])
        do = ok & ~stale
        self.evictions += int((do & ~present & vl.all(1)).sum())
        n_, s_, w_ = nodes[do], s[do], way[do]
        self.tags[n_, s_, w_] = keys[do]
        self.data_ts[n_, s_, w_] = ts[do]
        self.ins_ts[n_, s_, w_] = now
        self.origin[n_, s_, w_] = origin[do]
        self.valid[n_, s_, w_] = True
        self.dirty[n_, s_, w_] = False
        self.last_use[n_, s_, w_] = now
        self.data[n_, s_, w_] = data[do].astype(self.payload_dtype)

    def _sweep(self, keys, ts, data, lanes, now):
        """Coherence: every hearer that holds a broadcast key with an older
        timestamp updates it in place.  A hearer hears its own row and its
        K neighbours' rows that were delivered.  Returns the number of
        (hearer, row) pairs that updated; several rows updating one line
        leave the highest row's values."""
        n, k = self.n, self.k
        hearer = np.repeat(np.arange(n), k + 1)
        row = np.concatenate([np.arange(n)[:, None], self.nbr], 1).reshape(-1)
        live = np.concatenate([np.ones((n, 1), bool), lanes], 1).reshape(-1)
        hearer, row = hearer[live], row[live]
        s = (keys[row] % U32(self.sets)).astype(np.int64)
        upd = (self.valid[hearer, s] & (self.tags[hearer, s] == keys[row][:, None])
               & (ts[row][:, None] > self.data_ts[hearer, s]))
        count = int(upd.any(1).sum())
        p, w = np.nonzero(upd)
        flat = (hearer[p] * self.sets + s[p]) * self.ways + w
        win = np.full(self.tags.size, -1, np.int64)
        np.maximum.at(win, flat, row[p])
        lines = np.nonzero(win >= 0)[0]
        r = win[lines]
        self.data_ts.reshape(-1)[lines] = ts[r]
        self.last_use.reshape(-1)[lines] = now
        self.data.reshape(-1, self.dim)[lines] = data[r].astype(self.payload_dtype)
        return count

    # ---- writer ring ------------------------------------------------------------

    def _append(self, mask, keys, ts, origin):
        offs = np.cumsum(mask) - 1
        accept = mask & (offs < self.qcap - (self.tail - self.head))
        slots = (self.tail + offs[accept]) % self.qcap
        self.q_keys[slots] = keys[accept]
        self.q_ts[slots] = ts[accept]
        self.q_origin[slots] = origin[accept]
        return accept, offs

    def _enqueue(self, keys, ts, origin, mask):
        accept, _ = self._append(mask, keys, ts, origin)
        self.tail += int(accept.sum())
        self.dropped += int((mask & ~accept).sum())

    def _enqueue_keyed(self, kids, ts, origin, mask):
        """Last write of a key in the batch wins; a key with a pending slot
        is updated in place (coalesced); others append (dropped when full)."""
        order = np.arange(kids.size)
        last = np.full(self.ku, -1, np.int64)
        np.maximum.at(last, kids[mask], order[mask])
        rep = mask & (last[kids] == order)
        slot = self.slot_of_key[kids]
        pending = rep & (slot >= self.head) & (slot < self.tail)
        fresh = rep & ~pending
        at = slot[pending] % self.qcap
        self.q_keys[at] = kids[pending].astype(U32)
        self.q_ts[at] = ts[pending]
        self.q_origin[at] = origin[pending]
        accept, offs = self._append(fresh, kids.astype(U32), ts, origin)
        self.slot_of_key[kids[accept]] = self.tail + offs[accept]
        self.coalesced += int((mask & ~rep).sum() + pending.sum())
        self.dropped += int((fresh & ~accept).sum())
        self.tail += int(accept.sum())

    def _drain(self):
        """One writer tick: a token-bucket-limited batch append of up to
        ``max_drain`` rows, FIFO.  The store is always up, so every attempt
        succeeds and the writer never backs off."""
        self.tokens = min(np.float32(self.tokens + np.float32(self.rate)),
                          np.float32(self.burst))
        size = self.tail - self.head
        attempt = self.tokens >= 1.0 and size > 0
        n = min(size, self.max_drain) if attempt else 0
        self.head += n
        self.tokens = np.float32(self.tokens - np.float32(int(attempt)))
        return n, int(attempt)

    # ---- one tick -----------------------------------------------------------------

    def step(self) -> dict:
        n, t, k = self.n, self.t, self.k
        coalesced_before = self.coalesced
        rng_next, k_loss, k_age, k_src, k_resp, _ = self._draws()
        nodes = np.arange(n)

        # writes: every node writes one row per tick
        ts_w = np.full(n, t, np.int32)
        if self.zipf:
            with jax.default_device(self._cpu):
                k_wr = jax.random.fold_in(k_loss, WRITE_SALT)
            kids_w = self._key_ids(k_wr, (n,))
            keys_w = key_of_id(kids_w)
            data_w = versioned_payload(keys_w, ts_w, self.dim)
        else:
            keys_w = hash2(np.full(n, t), nodes)
            data_w = payload(keys_w, self.dim)
        all_w = np.ones(n, bool)
        self._insert(nodes, keys_w, ts_w, nodes.astype(np.int32), data_w, all_w, t)
        n_coh = 0
        if self.zipf:
            lanes = self._delivered(k_loss, (n, k))
            n_coh = self._sweep(keys_w, ts_w, data_w, lanes, t)
            self._enqueue_keyed(kids_w, ts_w, nodes.astype(np.int32), all_w)
            np.maximum.at(self.latest_ts, kids_w, ts_w)
        else:
            self._enqueue(keys_w, ts_w, nodes.astype(np.int32), all_w)
        self.cum_writes += n
        lan = _f32(n) * _f32(self.row_bytes)

        # reads: node i reads when (t + i) % period == 0, from tick 1
        reading = ((t + nodes) % self.period == 0) & (t > 0)
        if self.zipf:
            r_kids = self._key_ids(k_age, (n,))
            r_keys = key_of_id(r_kids)
        else:
            window = min(self.window_ticks, max(t, 1))
            ages = np.minimum(self._randint(k_age, (n,), window), t)
            src = self._randint(k_src, (n,), n)
            r_tick = (t - ages).astype(np.int64)
            r_keys = hash2(r_tick, src)
        slot_id = (-t) % self.period + self.period * np.arange(self.readers)
        slot_ok = (slot_id < n) & (t > 0)
        q = np.minimum(slot_id, n - 1)
        keys_q = r_keys[q]
        sidx = (keys_q % U32(self.sets)).astype(np.int64)

        # probe: the reader's own cache (lane 0) and its K neighbours
        cols = np.concatenate([q[:, None], self.nbr[q]], 1)
        match = self.valid[cols, sidx[:, None]] & (
            self.tags[cols, sidx[:, None]] == keys_q[:, None, None])
        hit = match.any(2)
        way = np.argmax(match, 2)
        ts_raw = self.data_ts[cols, sidx[:, None], way]
        hit_local = hit[:, 0] & slot_ok
        need_fog = slot_ok & ~hit_local
        ts_local = np.where(hit[:, 0], ts_raw[:, 0], -1)
        hit_fog = hit[:, 1:] & self._delivered(k_resp, (self.readers, k)) & need_fog[:, None]
        ts_fog = np.where(hit_fog, ts_raw[:, 1:], -1)
        best = np.argmax(ts_fog, 1)
        rr = np.arange(self.readers)
        fog_hit = hit_fog.any(1)
        best_ts = np.where(fog_hit, ts_fog[rr, best], -1)
        best_data = self.data[cols[rr, 1 + best], sidx, way[rr, 1 + best]]
        touch = np.concatenate([hit_local[:, None], hit_fog], 1)
        flat = ((cols * self.sets + sidx[:, None]) * self.ways + way)[touch]
        np.maximum.at(self.last_use.reshape(-1), flat, np.int32(t))
        n_resp = int(hit_fog.sum())
        n_queries = int(need_fog.sum())

        # fog misses: the writer's ring, then the store
        need_store = need_fog & ~fog_hit
        if self.zipf:
            kid_q = r_kids[q]
            slot = self.slot_of_key[kid_q]
        else:
            slot = (r_tick * n + src)[q]
        queue_hit = need_store & (slot >= self.head) & (slot < self.tail)
        store_read = need_store & ~queue_hit
        if self.zipf:
            durable = self.table_ts[kid_q]
            found = store_read & (durable >= 0)
            ring_ts = self.q_ts[np.maximum(slot, 0) % self.qcap]
            served_ts = np.where(queue_hit, ring_ts, np.where(found, durable, -1))
        else:
            found = store_read & (slot < self.drained_total)
        n_sr, n_qh = int(store_read.sum()), int(queue_hit.sum())
        lan = _f32(lan + _f32(n_queries * self.query_bytes))
        lan = _f32(lan + _f32((n_resp + n_qh) * self.row_bytes))
        wan_rx = _f32(n_sr) * self._read_txn(self.drained_total)
        self.api_calls += n_sr

        # fills land in the reader's cache
        fill = fog_hit | queue_hit | found
        if self.zipf:
            fill_data = np.where(fog_hit[:, None], best_data,
                                 versioned_payload(keys_q, served_ts, self.dim))
            fill_ts = np.where(fog_hit, best_ts, served_ts)
            fill_origin = np.full(self.readers, -1, np.int32)
        else:
            fill_data = np.where(fog_hit[:, None], best_data, payload(keys_q, self.dim))
            fill_ts = np.where(fog_hit, best_ts, r_tick[q])
            fill_origin = src[q].astype(np.int32)
        live = slot_id < n
        self._insert(slot_id[live], keys_q[live], fill_ts[live].astype(np.int32),
                     fill_origin[live], fill_data[live], fill[live], t)

        n_stale = 0
        if self.zipf:
            served = hit_local | fog_hit | queue_hit | found
            got = np.where(hit_local, ts_local, np.where(fog_hit, best_ts, served_ts))
            n_stale = int((served & (got < self.latest_ts[kid_q])).sum())

        # the writer drains a batch; the store commits it
        n_dr, calls = self._drain()
        self.drained_total += n_dr
        self.api_calls += calls
        if self.zipf and n_dr:
            idx = (self.head - n_dr + np.arange(n_dr)) % self.qcap
            np.maximum.at(self.table_ts, self.q_keys[idx].astype(np.int64), self.q_ts[idx])
        wan_tx = _f32(n_dr) * _f32(self.store_row_bytes)

        n_reads = int(reading.sum())
        n_local, n_fog = int(hit_local.sum()), int(fog_hit.sum())
        lat = _f32(_f32(_f32(n_local) * _f32(self.lat[0]))
                   + _f32(n_fog + n_qh) * _f32(self.lat[1]))
        lat = _f32(lat + _f32(n_sr) * _f32(self.lat[2]))
        base_rows = self.tail + self.dropped + self.coalesced
        baseline = _f32(_f32(n) * _f32(self.row_bytes)
                        + _f32(n_reads) * self._read_txn(base_rows))
        self.rng = rng_next
        self.t += 1
        return {
            "wan_tx_bytes": wan_tx, "wan_rx_bytes": wan_rx, "lan_bytes": lan,
            "reads": n_reads, "hits_local": n_local, "hits_fog": n_fog,
            "misses": n_sr, "store_found": int(found.sum()),
            "store_missing": int((store_read & ~found).sum()),
            "writes_gen": n, "writes_drained": n_dr,
            "queue_depth": self.tail - self.head, "queue_dropped": self.dropped,
            "store_txn_bytes": _f32(wan_rx + wan_tx), "store_txns": n_sr + calls,
            "read_latency_sum": lat, "baseline_wan_bytes": baseline,
            "hits_queue": n_qh, "ticks": 1, "coherence_updates": n_coh,
            "stale_reads": n_stale,
            "writes_coalesced": self.coalesced - coalesced_before,
            "churn_rejoins": 0, "wire_bytes": np.float32(0.0),
        }

    def _read_txn(self, rows):
        """A Sheets read fetches the whole sheet: every row stored so far."""
        return _f32(max(rows, 1)) * _f32(self.store_row_bytes)

    def chunk(self, ticks: int) -> dict:
        """``ticks`` steps folded into one row: flows summed, gauges last.
        The float fields are summed in ``payload_dtype`` (float32 as the
        configuration states; the control's lower precision otherwise)."""
        cast = np.dtype(self.payload_dtype).type
        agg = {f: 0 for f in INT_FIELDS}
        agg.update({f: cast(0.0) for f in FLOAT_FIELDS})
        for _ in range(ticks):
            m = self.step()
            for f in INT_FIELDS:
                agg[f] = m[f] if f in GAUGES else agg[f] + m[f]
            for f in FLOAT_FIELDS:
                agg[f] = cast(agg[f] + cast(m[f]))
        return agg

    def state(self) -> dict:
        """The state, under the names an engine's ``state_leaves`` uses."""
        return {
            "caches.tags": self.tags, "caches.data_ts": self.data_ts,
            "caches.ins_ts": self.ins_ts, "caches.origin": self.origin,
            "caches.valid": self.valid, "caches.dirty": self.dirty,
            "caches.last_use": self.last_use, "caches.data": self.data,
            "queue.keys": self.q_keys, "queue.data_ts": self.q_ts,
            "queue.origin": self.q_origin, "queue.head": self.head,
            "queue.tail": self.tail, "queue.dropped": self.dropped,
            "queue.backoff": 0, "queue.next_retry": 0,
            "queue.tokens": self.tokens, "queue.slot_of_key": self.slot_of_key,
            "queue.coalesced": self.coalesced,
            "store.drained_total": self.drained_total,
            "store.api_calls": self.api_calls,
            "store.outage_until": 0, "store.lost_writes": 0,
            "store.table_ts": self.table_ts,
            "tick": self.t, "rng": np.asarray(jax.device_get(self.rng)),
            "latest_ts": self.latest_ts, "plan.cum_writes": self.cum_writes,
        }
