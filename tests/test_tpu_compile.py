"""AOT compiles for a described TPU v5e: what the chip's compiler refuses.

Interpret mode accepts kernels that Mosaic rejects (unaligned blocks,
scalar stores to VMEM, ``argmax`` over bool), so the fog path's kernels and
the fused scan that calls them are compiled here for a ``v5e:2x2`` topology
described without a chip.  Nothing runs: these tests prove lowering and
compilation only.  The topology is described inside a module fixture, which
skips where the TPU compiler cannot be loaded.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SCENARIOS
from repro.core.simulator import SimConfig, _run_scan, init_sim
from repro.kernels.flic_insert import flic_insert_pallas
from repro.kernels.flic_lookup import flic_lookup_pallas
from repro.kernels.flic_update import flic_update_pallas

# The simulator's widths: S=50 sets x W=4 ways (200 lines), D=8 payload
# lanes; R=768 rows is ceil(N/15) for N=10^4 padded to the 128-row block.
S, W, D, R = 50, 4, 8, 768
N = 16  # node caches per call: the grid axis under vmap / node blocks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flic_lookup_compiles(one_chip):
    def probe(tags, ts, valid, data, keys, sidx):
        return jax.vmap(
            lambda a, b, c, d: flic_lookup_pallas(a, b, c, d, keys, sidx,
                                                  interpret=False)
        )(tags, ts, valid, data)

    hlo = _compiled_text(
        one_chip, probe,
        ((N, S, W), jnp.int32), ((N, S, W), jnp.int32),
        ((N, S, W), jnp.int32), ((N, S, W, D), jnp.float32),
        ((R,), jnp.int32), ((R,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


def test_flic_update_compiles(one_chip):
    def sweep(tags, ts, valid, lu, data, keys, sidx, row_ts, row_data,
              live, now):
        return jax.vmap(
            lambda a, b, c, d, e, lv: flic_update_pallas(
                a, b, c, d, e, keys, sidx, row_ts, row_data, lv, now,
                interpret=False)
        )(tags, ts, valid, lu, data, live)

    table = ((N, S, W), jnp.int32)
    hlo = _compiled_text(
        one_chip, sweep,
        table, table, table, table, ((N, S, W, D), jnp.float32),
        ((R,), jnp.int32), ((R,), jnp.int32), ((R,), jnp.int32),
        ((R, D), jnp.float32), ((N, R), jnp.bool_), ((1,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [N, 13])  # a divisor block and a prime N
def test_flic_insert_compiles(one_chip, n):
    table = ((n, S, W), jnp.int32)
    flag = ((n, S, W), jnp.bool_)
    per_node = ((n,), jnp.int32)
    hlo = _compiled_text(
        one_chip, lambda *a: flic_insert_pallas(*a, interpret=False),
        table, table, table, table, flag, flag, table,
        ((n, S, W, D), jnp.float32),
        per_node, per_node, per_node, per_node,
        ((n,), jnp.bool_), ((n,), jnp.bool_), ((n, D), jnp.float32),
        ((), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("scenario", ["paper", "zipf_hot"])
def test_fused_scan_with_pallas_kernels_compiles(one_chip, scenario):
    """The whole fused scan with every kernel on the chip path: lookup and
    insert on ``paper``, plus the coherence sweep on ``zipf_hot``."""
    cfg = SimConfig(n_nodes=32, cache_lines=200, payload_dim=8,
                    probe_backend="pallas",
                    workload=dataclasses.replace(SCENARIOS[scenario], fanout=8))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_sim(cfg)),
    )
    hlo = _run_scan.lower(cfg, 30, state, 1, "fused").compile().as_text()
    assert "tpu_custom_call" in hlo
