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
import re

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


def _fused_scan_text(one_chip, payload_dim, scenario="zipf_hot"):
    cfg = SimConfig(n_nodes=32, cache_lines=200, payload_dim=payload_dim,
                    workload=dataclasses.replace(SCENARIOS[scenario], fanout=8))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_sim(cfg)),
    )
    return cfg, _run_scan.lower(cfg, 4, state, 1, "fused").compile().as_text()


def _loop_body_ops(hlo):
    """(innermost stage or None, result shape) of every instruction in the
    compiled loop bodies; a tuple-shaped result gives the empty shape."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    assert bodies
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        if comp not in bodies:
            continue
        op = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\w+\[([\d,]*)\])?", line)
        if op is None:
            continue
        scope = re.search(r'op_name="([^"]*)"', line)
        stages = re.findall(r"stage\.(\w+)", scope.group(1)) if scope else []
        dims = op.group(1)
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        yield (stages[-1] if stages else None), shape


def test_fused_scan_keeps_every_stage_in_its_loop_body(one_chip):
    """The tick's named stages survive the chip's compiler: each of the five
    owns an instruction of the compiled loop body (by the innermost
    ``stage.<x>`` of its ``op_name``), on a mutable Zipf config whose
    coherence sweep runs."""
    _, hlo = _fused_scan_text(one_chip, 8)
    found = {stage for stage, _ in _loop_body_ops(hlo)}
    assert {"plan", "probe", "sweep", "upsert", "writer"} <= found


@pytest.mark.parametrize("scenario", ["paper", "zipf_hot"])
def test_fused_upsert_writes_tables_in_their_own_shape(one_chip, scenario):
    """At the paper's 37 payload lanes, no instruction of the upsert views a
    cache table as flat lines, ``[N*S*W]`` or ``[N*S*W, D]``: on the chip
    such a view relays the whole table out and back every call.  The scan
    carries the seven ``[N, S, W]`` tables node-minor, so their 4 ways are
    not padded to 128 lanes (left to itself, the compiler holds the write-once
    stream's tables W-minor), and every stage the scenario runs still owns
    instructions of the loop body."""
    cfg, hlo = _fused_scan_text(one_chip, 37, scenario)
    lines = cfg.n_nodes * cfg.cache_lines
    ops = list(_loop_body_ops(hlo))
    flat = [shape for stage, shape in ops
            if stage == "upsert" and shape in ((lines,), (lines, cfg.payload_dim))]
    assert not flat
    table = rf"\w+\[{cfg.n_nodes},{cfg.cache_sets},{cfg.cache_ways}\]\{{([\d,]+)"
    carried = [re.findall(table, carry)
               for carry in re.findall(r"= \((.*?)\) while\(", hlo)]
    assert [layouts for layouts in carried if layouts] == [["0,2,1"] * 7]
    stages = {"plan", "probe", "upsert", "writer"} | (
        {"sweep"} if cfg.workload.mutable else set())
    assert stages <= {s for s, _ in ops}
