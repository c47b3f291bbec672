"""Pallas TPU kernel: batched FLIC one-line-per-node upsert.

The fused engine's two remaining per-tick upsert scatters — the own-row
wave insert and the reader fill (``flic.insert_rows`` at both ``sim_tick``
call sites) — write all EIGHT cache tables through one flat scatter each.
This kernel fuses the whole upsert into one VMEM-pinned pass: way select
(first-matching-way, first-invalid-else-LRU victim), the strictly-newer
timestamp gate, and the eight per-field row writes, with every table
buffer donated (``input_output_aliases``), so the simulator's scan reuses
the cache-state memory with no per-field scatter traffic.

TPU mapping (DESIGN.md §2/§4): the grid walks node blocks of ``N_BLOCK``
nodes; each grid step holds its (N_BLOCK, S, W[, D]) table blocks in VMEM
and its per-node scalars (key, set index, line ts/origin/dirty, live) in
an SMEM block, copies the tables input→output once, then each node
touches exactly its own probed set row via one-row dynamic sublane
slices; way selects are lane mins over ``where(mask, iota, W)``.  Nodes
touch disjoint rows, so the sequential node loop has no ordering hazard
and the pass is bit-identical to the inline ``insert_rows`` scatters and
the ``kernels/ref.py`` oracle for arbitrary inputs.

Eviction records are NOT produced: both engine call sites discard them,
and skipping the displaced-line gather is what lets all eight tables be
donated whole (``flic.insert_rows`` documents the kernel-path contract).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Nodes per grid step.  VMEM tiles pad the trailing two dims to (8, 128):
# at the default geometry (S=50, W=4, D=8) a node's seven int tables take
# 7 x 28 KB and its payload table 200 KB, and the input and output blocks
# are each double-buffered, so a step holds ~12.7 MB — inside the
# compiler's VMEM limit on a TPU v5e.  The wrapper drops to the largest
# divisor of N at or under this bound, so no node padding is needed.
N_BLOCK = 8
N_SCALARS = 6  # key, set index, line ts, line origin, line dirty, live


def _node_block(n: int) -> int:
    for nb in range(min(N_BLOCK, n), 0, -1):
        if n % nb == 0:
            return nb
    return 1


def _kernel(now_ref, scal_ref,
            tags_in, ts_in, ins_in, org_in, val_in, dir_in, lu_in,
            line_data_ref, data_in,
            tags_out, ts_out, ins_out, org_out, val_out, dir_out, lu_out,
            data_out):
    nb = tags_in.shape[0]
    w = tags_in.shape[-1]

    # Copy this node block input -> output (identity under donation), then
    # the node loop reads and writes the OUT refs only: each node's single
    # row write happens after its reads, and rows are disjoint across nodes.
    tags_out[...] = tags_in[...]
    ts_out[...] = ts_in[...]
    ins_out[...] = ins_in[...]
    org_out[...] = org_in[...]
    val_out[...] = val_in[...]
    dir_out[...] = dir_in[...]
    lu_out[...] = lu_in[...]
    data_out[...] = data_in[...]

    now = now_ref[0]
    int_max = jnp.iinfo(jnp.int32).max
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)          # (1, W)

    def first(mask):  # first True lane as (1, 1); W when none (argmax-free)
        return jnp.min(jnp.where(mask, lane, w), axis=1, keepdims=True)

    def body(j, _):
        key = scal_ref[j, 0, 0]
        s = scal_ref[j, 0, 1]
        lts = scal_ref[j, 0, 2]
        lorg = scal_ref[j, 0, 3]
        ldir = scal_ref[j, 0, 4]
        lv = scal_ref[j, 0, 5] != 0
        row = (j, pl.ds(s, 1), slice(None))
        row_tags = tags_out[row]                                   # (1, W)
        row_ts = ts_out[row]
        row_ins = ins_out[row]
        row_org = org_out[row]
        row_val = val_out[row]
        row_dir = dir_out[row]
        row_use = lu_out[row]

        valid = row_val != 0
        match = valid & (row_tags == key)
        present = jnp.any(match, axis=1, keepdims=True)            # (1, 1)
        present_way = first(match)                                 # first way
        any_inv = jnp.any(~valid, axis=1, keepdims=True)
        inv_way = first(~valid)                                    # first invalid
        use = jnp.where(valid, row_use, int_max)
        lru_way = first(use == jnp.min(use, axis=1, keepdims=True))
        victim = jnp.where(any_inv, inv_way, lru_way)
        way = jnp.where(present, present_way, victim)              # (1, 1)

        sel = lane == way                                          # (1, W)
        old_ts = jnp.sum(jnp.where(sel, row_ts, 0), axis=1, keepdims=True)
        stale = present & (lts <= old_ts)
        wr = sel & (lv & ~stale)                                   # (1, W)

        tags_out[row] = jnp.where(wr, key, row_tags)
        ts_out[row] = jnp.where(wr, lts, row_ts)
        ins_out[row] = jnp.where(wr, now, row_ins)
        org_out[row] = jnp.where(wr, lorg, row_org)
        val_out[row] = jnp.where(wr, 1, row_val)
        dir_out[row] = jnp.where(wr, ldir, row_dir)
        lu_out[row] = jnp.where(wr, now, row_use)

        ld = line_data_ref[j]                                      # (1, D)
        for k in range(w):
            cur = data_out[j, s, pl.ds(k, 1), :]                   # (1, D)
            data_out[j, s, pl.ds(k, 1), :] = jnp.where(wr[:, k:k + 1], ld, cur)
        return 0

    jax.lax.fori_loop(0, nb, body, 0)


@partial(jax.jit, static_argnames=("interpret",))
def flic_insert_pallas(
    tags: jax.Array,         # (N, S, W) int32
    data_ts: jax.Array,      # (N, S, W) int32
    ins_ts: jax.Array,       # (N, S, W) int32
    origin: jax.Array,       # (N, S, W) int32
    valid: jax.Array,        # (N, S, W) bool
    dirty: jax.Array,        # (N, S, W) bool
    last_use: jax.Array,     # (N, S, W) int32
    data: jax.Array,         # (N, S, W, D) f32
    keys: jax.Array,         # (N,) int32
    sidx: jax.Array,         # (N,) int32
    line_ts: jax.Array,      # (N,) int32
    line_origin: jax.Array,  # (N,) int32
    line_dirty: jax.Array,   # (N,) bool
    live: jax.Array,         # (N,) bool — lines.valid; False lanes are no-ops
    line_data: jax.Array,    # (N, D) f32
    now: jax.Array,          # int32 scalar
    interpret: bool = True,
):
    n, s, w = tags.shape
    d = data.shape[-1]
    nb = _node_block(n)
    grid = (n // nb,)

    tab = pl.BlockSpec((nb, s, w), lambda i, *_: (i, 0, 0))
    tab3 = pl.BlockSpec((nb, s, w, d), lambda i, *_: (i, 0, 0, 0))
    # The per-node scalars travel packed as (N, 1, 6) so a node block is an
    # SMEM block whose trailing two dims are whole (no 128-lane rank-1 rule).
    scal = jnp.stack([
        keys, sidx, line_ts, line_origin,
        line_dirty.astype(jnp.int32), live.astype(jnp.int32),
    ], axis=-1).astype(jnp.int32)[:, None, :]
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                  # now -> SMEM
            grid=(n // nb,),
            in_specs=[
                pl.BlockSpec((nb, 1, N_SCALARS), lambda i, *_: (i, 0, 0),
                             memory_space=pltpu.SMEM),  # per-node scalars
                tab,                                # tags      (donated)
                tab,                                # data_ts   (donated)
                tab,                                # ins_ts    (donated)
                tab,                                # origin    (donated)
                tab,                                # valid     (donated)
                tab,                                # dirty     (donated)
                tab,                                # last_use  (donated)
                pl.BlockSpec((nb, 1, d), lambda i, *_: (i, 0, 0)),  # line_data
                tab3,                               # data      (donated)
            ],
            out_specs=[tab, tab, tab, tab, tab, tab, tab, tab3],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, s, w), jnp.int32),   # tags
            jax.ShapeDtypeStruct((n, s, w), jnp.int32),   # data_ts
            jax.ShapeDtypeStruct((n, s, w), jnp.int32),   # ins_ts
            jax.ShapeDtypeStruct((n, s, w), jnp.int32),   # origin
            jax.ShapeDtypeStruct((n, s, w), jnp.int32),   # valid
            jax.ShapeDtypeStruct((n, s, w), jnp.int32),   # dirty
            jax.ShapeDtypeStruct((n, s, w), jnp.int32),   # last_use
            jax.ShapeDtypeStruct((n, s, w, d), data.dtype),
        ],
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3, 6: 4, 7: 5, 8: 6, 10: 7},
        interpret=interpret,
    )(
        jnp.full((1,), jnp.asarray(now, jnp.int32)), scal,
        tags, data_ts, ins_ts, origin,
        valid.astype(jnp.int32), dirty.astype(jnp.int32), last_use,
        line_data[:, None, :], data,
    )
    (n_tags, n_ts, n_ins, n_org, n_val, n_dir, n_lu, n_data) = out
    return (n_tags, n_ts, n_ins, n_org, n_val.astype(bool),
            n_dir.astype(bool), n_lu, n_data)
