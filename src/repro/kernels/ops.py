"""Jitted entry points for the Pallas kernels with backend dispatch.

``backend``:
  * ``"pallas"``      — real TPU lowering (pl.pallas_call, BlockSpec VMEM);
  * ``"interpret"``   — the same kernel body executed in Python on CPU
                         (what this container runs; numerics identical);
  * ``"xla"``         — the pure-jnp oracle from ``ref.py``.

Default: interpret on CPU hosts, pallas on TPU.  ``REPRO_KERNELS=xla``
forces the oracle (used by the serving engine's dry-run lowering, since a
TPU kernel cannot lower on the CPU AOT path).  ``REPRO_KERNELS=interpret``
is refused on a TPU, so a chip run never goes through the interpreter
unseen.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.flic_insert import N_BLOCK as FLIC_INSERT_BLOCK
from repro.kernels.flic_insert import flic_insert_pallas
from repro.kernels.flic_lookup import Q_BLOCK as FLIC_LOOKUP_BLOCK
from repro.kernels.flic_lookup import flic_lookup_pallas
from repro.kernels.flic_merge import flic_merge_pallas
from repro.kernels.flic_update import R_BLOCK as FLIC_UPDATE_BLOCK
from repro.kernels.flic_update import flic_update_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _mode() -> str:
    on_tpu = jax.default_backend() == "tpu"
    env = os.environ.get("REPRO_KERNELS")
    if env == "interpret" and on_tpu:
        raise RuntimeError(
            "REPRO_KERNELS=interpret on a TPU would run every kernel in the "
            "Pallas interpreter; unset it (kernels then lower for the chip) "
            "or pick backend='interpret' explicitly"
        )
    if env:
        return env
    return "pallas" if on_tpu else "interpret"


def flic_lookup(tags, data_ts, valid, data, keys, sidx, backend: str | None = None):
    """Batched probe; returns (hit, ts, payload, way) — see ref.flic_lookup_ref."""
    mode = backend or _mode()
    if mode == "xla":
        return ref.flic_lookup_ref(tags, data_ts, valid, data, keys, sidx)
    return flic_lookup_pallas(
        tags, data_ts, valid, data, keys, sidx, interpret=(mode != "pallas")
    )


def flic_update(tags, data_ts, valid, last_use, data, keys, sidx, row_ts,
                row_data, live, now, backend: str | None = None):
    """One cache's coherence-update sweep; returns (data_ts, last_use, data,
    n_updates) — see ref.flic_update_ref for the exact contract."""
    mode = backend or _mode()
    if mode == "xla":
        return ref.flic_update_ref(
            tags, data_ts, valid, last_use, data, keys, sidx, row_ts,
            row_data, live, now,
        )
    return flic_update_pallas(
        tags, data_ts, valid, last_use, data, keys, sidx, row_ts,
        row_data, live, now, interpret=(mode != "pallas"),
    )


def flic_insert(tags, data_ts, ins_ts, origin, valid, dirty, last_use, data,
                keys, sidx, line_ts, line_origin, line_dirty, live, line_data,
                now, backend: str | None = None):
    """Batched one-line-per-node upsert; returns the eight updated tables —
    see ref.flic_insert_ref for the exact contract."""
    mode = backend or _mode()
    if mode == "xla":
        return ref.flic_insert_ref(
            tags, data_ts, ins_ts, origin, valid, dirty, last_use, data,
            keys, sidx, line_ts, line_origin, line_dirty, live, line_data, now,
        )
    return flic_insert_pallas(
        tags, data_ts, ins_ts, origin, valid, dirty, last_use, data,
        keys, sidx, line_ts, line_origin, line_dirty, live, line_data, now,
        interpret=(mode != "pallas"),
    )


def flic_merge(tags_a, ts_a, valid_a, data_a, tags_b, ts_b, valid_b, data_b,
               backend: str | None = None):
    mode = backend or _mode()
    if mode == "xla":
        return ref.flic_merge_ref(
            tags_a, ts_a, valid_a, data_a, tags_b, ts_b, valid_b, data_b
        )
    return flic_merge_pallas(
        tags_a, ts_a, valid_a, data_a, tags_b, ts_b, valid_b, data_b,
        interpret=(mode != "pallas"),
    )


def paged_attention(q, k_pages, v_pages, page_table, lengths,
                    backend: str | None = None):
    mode = backend or _mode()
    if mode == "xla":
        return ref.paged_attention_ref(q, k_pages, v_pages, page_table, lengths)
    return paged_attention_pallas(
        q, k_pages, v_pages, page_table, lengths, interpret=(mode != "pallas")
    )


def ssd_scan(states, chunk_decay, init=None, backend: str | None = None):
    mode = backend or _mode()
    if mode == "xla":
        return ref.ssd_scan_ref(states, chunk_decay, init)
    return ssd_scan_pallas(states, chunk_decay, init, interpret=(mode != "pallas"))
