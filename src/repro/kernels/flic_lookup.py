"""Pallas TPU kernel: batched set-associative FLIC cache probe.

The fog-read hot loop (paper §II-A): for a block of queries, locate each
key's set, tag-compare across ways, and return the max-timestamp matching
line (soft-coherence tie-break) plus its payload.

TPU mapping (DESIGN.md §2/§4): the query keys and set indices are
scalar-prefetched into SMEM (``PrefetchScalarGridSpec``), so each query's
set index addresses a one-row dynamic sublane slice of the (S, W) tables,
which sit whole in VMEM for the block.  The way-select is a (1, W) VPU
compare; the first winning way is a lane-min over ``where(win, iota, W)``
(``argmax`` over bool does not lower).  Per-query hit / ts / way are
accumulated as (1, Q_BLOCK) lane vectors and written once per block; the
payload row is one (1, D) store.  No MXU needed.

Block sizes: Q_BLOCK queries per grid step; the whole (S, W) table per step
(index_map pins block 0) — correct while S*W*(12+4D) bytes, padded to
(8, 128) tiles, fits VMEM, which holds for every simulator geometry.
Under ``vmap`` (one call per node cache) the node axis becomes an extra
grid axis and the shared query arrays stay unbatched in SMEM.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Q_BLOCK = 128


def _kernel(q_ref, sidx_ref, tags_ref, ts_ref, valid_ref, data_ref,
            hit_ref, ts_out_ref, payload_ref, way_ref):
    qb = hit_ref.shape[-1]
    w = tags_ref.shape[1]
    base = pl.program_id(0) * qb
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    lane_q = jax.lax.broadcasted_iota(jnp.int32, (1, qb), 1)

    def body(i, carry):
        hit_v, ts_v, way_v = carry
        key = q_ref[base + i]
        s = sidx_ref[base + i]
        row_tags = tags_ref[pl.ds(s, 1), :]                       # (1, W)
        row_valid = valid_ref[pl.ds(s, 1), :]
        row_ts = ts_ref[pl.ds(s, 1), :]
        match = (row_valid != 0) & (row_tags == key)
        ts_m = jnp.where(match, row_ts, -1)
        hit = jnp.max(match.astype(jnp.int32), axis=1, keepdims=True) > 0
        best = jnp.max(ts_m, axis=1, keepdims=True)                # (1, 1)
        onehot = (ts_m == best) & match
        # resolve duplicates-with-equal-ts deterministically: first way wins
        first = jnp.min(jnp.where(onehot, lane_w, w), axis=1, keepdims=True)
        row_data = data_ref[s]                                     # (W, D)
        payload = jnp.zeros((1, row_data.shape[-1]), row_data.dtype)
        for k in range(w):  # one-hot pick: exact copy of the winning way
            payload = jnp.where(first == k, row_data[k:k + 1, :], payload)
        payload_ref[pl.ds(i, 1), :] = jnp.where(hit, payload, 0.0)
        at = lane_q == i
        hit_v = jnp.where(at, hit.astype(jnp.int32), hit_v)
        ts_v = jnp.where(at, jnp.where(hit, best, -1), ts_v)
        # winning way (0 on miss) — the caller's LRU-touch scatter needs it
        way_v = jnp.where(at, jnp.where(hit, first, 0), way_v)
        return hit_v, ts_v, way_v

    zeros = jnp.zeros((1, qb), jnp.int32)
    hit_v, ts_v, way_v = jax.lax.fori_loop(0, qb, body, (zeros, zeros, zeros))
    hit_ref[...] = hit_v
    ts_out_ref[...] = ts_v
    way_ref[...] = way_v


@partial(jax.jit, static_argnames=("interpret",))
def flic_lookup_pallas(
    tags: jax.Array,     # (S, W) int32
    data_ts: jax.Array,  # (S, W) int32
    valid: jax.Array,    # (S, W) int32/bool
    data: jax.Array,     # (S, W, D) f32
    keys: jax.Array,     # (Q,) int32
    sidx: jax.Array,     # (Q,) int32
    interpret: bool = True,
):
    s, w = tags.shape
    d = data.shape[-1]
    q = keys.shape[0]
    qb = min(Q_BLOCK, q)
    assert q % qb == 0, (q, qb)

    full = pl.BlockSpec((s, w), lambda i, *_: (0, 0))
    lanes = pl.BlockSpec((1, qb), lambda i, *_: (0, i))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                  # keys, sidx -> SMEM
            grid=(q // qb,),
            in_specs=[
                full, full, full,
                pl.BlockSpec((s, w, d), lambda i, *_: (0, 0, 0)),
            ],
            out_specs=[
                lanes, lanes,
                pl.BlockSpec((qb, d), lambda i, *_: (i, 0)),
                lanes,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, q), jnp.int32),
            jax.ShapeDtypeStruct((1, q), jnp.int32),
            jax.ShapeDtypeStruct((q, d), data.dtype),
            jax.ShapeDtypeStruct((1, q), jnp.int32),
        ],
        interpret=interpret,
    )(keys, sidx, tags, data_ts, valid.astype(jnp.int32), data)
    hit, ts, payload, way = out
    return hit[0].astype(bool), ts[0], payload, way[0]
