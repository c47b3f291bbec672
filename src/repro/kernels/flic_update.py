"""Pallas TPU kernel: batched FLIC coherence-update sweep.

The directory policy's per-tick coherence pass (paper §I.A.a): R broadcast
rows sweep one node's set-associative cache; every resident copy of a row's
key with a strictly OLDER timestamp is updated in place (timestamp, LRU
stamp, payload) — no insert, no eviction.  This is the mutable-scenario hot
loop: on ``zipf_hot`` the sweep applies ~1M updates per 600-tick run.

TPU mapping (DESIGN.md §2/§3/§4): the row keys, set indices, timestamps and
``now`` are scalar-prefetched into SMEM (``PrefetchScalarGridSpec``; they
are shared by every node cache, so they stay unbatched under ``vmap``), the
per-cache ``live`` flags arrive as a (1, R_BLOCK) VMEM lane row (an SMEM
block would need a squeezed second-minor dim under ``vmap``, which the
block rules refuse), and the applied-update count accumulates in a (1, 1)
SMEM output.  The cache tables
(tags/ts/valid/last_use) sit whole in VMEM for the sweep and the payload
tile streams once; each row addresses its set through a one-row dynamic
sublane slice, way-select is a (1, W)-lane VPU select, and the payload
write is one (1, D) store per way.  The sequential row loop gives
last-qualifying-row-wins per line, and every qualification is judged
against the PRE-sweep timestamps (the un-aliased ``ts_in`` block), which is
exactly the ``winr`` winner election of the inline path and the
``kernels/ref.py`` oracle — so all backends are bit-identical, including
the applied-update count.

Buffer donation: ``last_use`` and ``data`` are write-only after the first
grid step, so their input buffers are donated to the outputs
(``input_output_aliases``) and XLA reuses the cache-state memory across the
simulator's scan.  ``data_ts`` is NOT donated — the pre-sweep timestamps
are read throughout.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

R_BLOCK = 128


def _kernel(keys_ref, sidx_ref, row_ts_ref, now_ref,
            live_ref, tags_ref, ts_in_ref, valid_ref, lu_in_ref, row_data_ref,
            data_in_ref,
            ts_out_ref, lu_out_ref, data_out_ref, cnt_ref):
    rb = row_data_ref.shape[0]
    w = tags_ref.shape[1]
    base = pl.program_id(0) * rb
    lane_r = jax.lax.broadcasted_iota(jnp.int32, (1, rb), 1)
    live = live_ref[...]                                           # (1, RB)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        ts_out_ref[...] = ts_in_ref[...]
        lu_out_ref[...] = lu_in_ref[...]
        data_out_ref[...] = data_in_ref[...]
        cnt_ref[0, 0] = 0

    now = now_ref[0]

    def body(i, cnt):
        key = keys_ref[base + i]
        s = sidx_ref[base + i]
        rts = row_ts_ref[base + i]
        lv = jnp.max(jnp.where(lane_r == i, live, 0), axis=1, keepdims=True) != 0
        row_tags = tags_ref[pl.ds(s, 1), :]                        # (1, W)
        row_valid = valid_ref[pl.ds(s, 1), :]
        orig_ts = ts_in_ref[pl.ds(s, 1), :]                        # PRE-sweep
        upd = (row_valid != 0) & (row_tags == key) & (rts > orig_ts) & lv

        ts_out_ref[pl.ds(s, 1), :] = jnp.where(upd, rts, ts_out_ref[pl.ds(s, 1), :])
        lu_out_ref[pl.ds(s, 1), :] = jnp.where(upd, now, lu_out_ref[pl.ds(s, 1), :])
        rd = row_data_ref[pl.ds(i, 1), :]                          # (1, D)
        for k in range(w):
            cur = data_out_ref[s, pl.ds(k, 1), :]                  # (1, D)
            data_out_ref[s, pl.ds(k, 1), :] = jnp.where(upd[:, k:k + 1], rd, cur)
        return cnt + jnp.max(upd.astype(jnp.int32))

    cnt_ref[0, 0] = cnt_ref[0, 0] + jax.lax.fori_loop(0, rb, body, jnp.int32(0))


@partial(jax.jit, static_argnames=("interpret",))
def flic_update_pallas(
    tags: jax.Array,      # (S, W) int32
    data_ts: jax.Array,   # (S, W) int32
    valid: jax.Array,     # (S, W) int32/bool
    last_use: jax.Array,  # (S, W) int32
    data: jax.Array,      # (S, W, D) f32
    keys: jax.Array,      # (R,) int32
    sidx: jax.Array,      # (R,) int32
    row_ts: jax.Array,    # (R,) int32
    row_data: jax.Array,  # (R, D) f32
    live: jax.Array,      # (R,) bool
    now: jax.Array,       # (1,) int32
    interpret: bool = True,
):
    s, w = tags.shape
    d = data.shape[-1]
    r = keys.shape[0]
    rb = min(R_BLOCK, r)
    assert r % rb == 0, (r, rb)
    grid = (r // rb,)

    full = pl.BlockSpec((s, w), lambda i, *_: (0, 0))
    full3 = pl.BlockSpec((s, w, d), lambda i, *_: (0, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,                  # keys, sidx, row_ts, now
            grid=(r // rb,),
            in_specs=[
                pl.BlockSpec((1, rb), lambda i, *_: (0, i)),    # live
                full,                                           # tags
                full,                                           # data_ts (pre-sweep)
                full,                                           # valid
                full,                                           # last_use (donated)
                pl.BlockSpec((rb, d), lambda i, *_: (i, 0)),    # row_data
                full3,                                          # data (donated)
            ],
            out_specs=[
                full,                                           # data_ts out
                full,                                           # last_use out
                full3,                                          # data out
                pl.BlockSpec((1, 1), lambda i, *_: (0, 0),
                             memory_space=pltpu.SMEM),          # count
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s, w), jnp.int32),
            jax.ShapeDtypeStruct((s, w), jnp.int32),
            jax.ShapeDtypeStruct((s, w, d), data.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        input_output_aliases={8: 1, 10: 2},         # last_use, data
        interpret=interpret,
    )(
        keys, sidx, row_ts, now, live.astype(jnp.int32)[None, :],
        tags, data_ts, valid.astype(jnp.int32), last_use, row_data, data,
    )
    new_ts, new_lu, new_data, cnt = out
    return new_ts, new_lu, new_data, cnt[0, 0]
