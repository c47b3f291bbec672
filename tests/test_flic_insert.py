"""The ``flic_insert`` kernel contract: inline == oracle == Pallas kernel.

``flic.insert_rows`` has three executions of ONE deterministic semantics
(DESIGN.md §3/§9): the inline gather + flat-scatter upsert, the pure-jnp
oracle ``kernels.ref.flic_insert_ref``, and the Pallas kernel
``kernels/flic_insert.py`` (interpret mode on CPU).  Way select is
first-matching-way on a hit and first-invalid-else-LRU otherwise; a present
line is overwritten only by a STRICTLY newer timestamp; dead lanes
(``lines.valid`` False) never write — so the contract is exact bit-identity
of all eight cache tables across backends for ARBITRARY inputs, including
duplicate resident keys, LRU ties, stale incoming lines and masked lanes.
The inline path is itself pinned to ``jax.vmap(insert)`` (the scalar
soft-coherence upsert) so all four formulations agree.

The hypothesis sweep drives random (N, S, W, occupancy) shapes through all
three backends; fixed cases cover the non-divisor node-block path
(N % N_BLOCK != 0 ⇒ the wrapper drops to a divisor block), the in-place
update vs stale no-op branch, and the eviction-record contract
(kernel path returns ``evictions=None``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # the fixed-case tests below still run without it
    HAVE_HYPOTHESIS = False

    def given(**kw):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(**kw):
        return lambda f: f

    class _St:  # stands in for strategy constructors at decoration time
        def __getattr__(self, name):
            return lambda *a, **kw: None

    st = _St()

from repro.core.cache_state import CacheLine, empty_cache
from repro.core.flic import insert, insert_rows

SETTINGS = dict(max_examples=20, deadline=None)

KERNEL_BACKENDS = ("xla", "interpret")

FIELDS = ("tags", "data_ts", "ins_ts", "origin", "valid", "dirty",
          "last_use", "data")


def _random_state(rng, n, s, w, d, key_pool, fill=0.6):
    """A populated cache batch plus one incoming line per node over a small
    key pool (small pool ⇒ frequent present-key hits and set collisions)."""
    caches = empty_cache(s, w, d, jnp.float32, batch=(n,))
    occupied = rng.random((n, s, w)) < fill
    caches = dataclasses.replace(
        caches,
        tags=jnp.asarray(np.where(
            occupied, rng.choice(key_pool, (n, s, w)), 0xFFFFFFFF
        ).astype(np.uint32)),
        data_ts=jnp.asarray(rng.integers(-1, 50, (n, s, w)), jnp.int32),
        ins_ts=jnp.asarray(rng.integers(-1, 50, (n, s, w)), jnp.int32),
        origin=jnp.asarray(rng.integers(-1, n, (n, s, w)), jnp.int32),
        valid=jnp.asarray(occupied),
        dirty=jnp.asarray(rng.random((n, s, w)) < 0.3),
        last_use=jnp.asarray(rng.integers(-1, 50, (n, s, w)), jnp.int32),
        data=jnp.asarray(rng.standard_normal((n, s, w, d)), jnp.float32),
    )
    lines = CacheLine(
        key=jnp.asarray(rng.choice(key_pool, (n,)), jnp.uint32),
        data_ts=jnp.asarray(rng.integers(0, 80, (n,)), jnp.int32),
        origin=jnp.asarray(rng.integers(0, n, (n,)), jnp.int32),
        data=jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
        valid=jnp.asarray(rng.random(n) < 0.85),
        dirty=jnp.asarray(rng.random(n) < 0.5),
    )
    return caches, lines


def _assert_same_upsert(caches, lines, now, backends=KERNEL_BACKENDS):
    ref_c, _ = insert_rows(caches, lines, now)
    for be in backends:
        ker_c, ev = insert_rows(caches, lines, now, backend=be)
        assert ev is None, f"{be}: kernel path must not build evictions"
        for f in FIELDS:
            a, b = getattr(ref_c, f), getattr(ker_c, f)
            assert a.dtype == b.dtype, f"{be}: caches.{f} dtype"
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"{be}: caches.{f}"
            )
    return ref_c


@settings(**SETTINGS)
@given(
    seed=st.integers(0, 2**16),
    n=st.integers(1, 19),
    s=st.sampled_from([2, 4, 8]),
    w=st.sampled_from([1, 2, 4]),
    pool=st.integers(3, 12),
)
def test_insert_rows_kernel_matches_inline(seed, n, s, w, pool):
    """Random states through all three backends — n spans divisor and
    non-divisor node-block sizes (N_BLOCK=8)."""
    rng = np.random.default_rng(seed)
    key_pool = rng.integers(0, 2**32, pool, dtype=np.uint32)
    caches, lines = _random_state(rng, n, s, w, 4, key_pool)
    _assert_same_upsert(caches, lines, jnp.int32(99))


# (seed, N, S, W, D, fill): the first three are the original small cases;
# the rest run the cells' payload widths (37 lanes = the paper's 148-B row,
# 250 = YCSB's 1000-B record) at node counts that divide the kernel's
# 8-node block and that do not.
INLINE_CASES = [
    (0, 6, 4, 2, 4, 0.6),
    (1, 6, 4, 2, 4, 0.6),
    (2, 6, 4, 2, 4, 0.6),
    (3, 8, 4, 4, 37, 0.9),
    (4, 13, 4, 4, 37, 0.9),
    (5, 16, 2, 4, 250, 0.9),
    (6, 5, 4, 2, 250, 0.8),
    (7, 24, 8, 4, 37, 0.95),
    (8, 11, 2, 2, 4, 0.7),
]


def _plant_branches(caches, lines, s):
    """Lanes 0-3 take each branch of the upsert on top of a random state:
    a present key overwritten by a strictly newer row, a present key met by
    a row of equal timestamp (stale), a masked lane, and a new key into a
    full set (an LRU victim).  Returns the planted (caches, lines, sets)."""
    c = {f: np.array(getattr(caches, f)) for f in FIELDS}
    ln = {f: np.array(getattr(lines, f))
          for f in ("key", "data_ts", "origin", "data", "valid", "dirty")}
    sets = ln["key"][:4] % np.uint32(s)
    for node, ts in ((0, 10), (1, 30)):        # present: newer, then stale
        c["tags"][node, sets[node], 0] = ln["key"][node]
        c["valid"][node, sets[node], 0] = True
        c["data_ts"][node, sets[node], 0] = ts
    ln["data_ts"][:2] = 20, 30
    ln["valid"][[0, 1, 3]] = True
    ln["valid"][2] = False                     # masked
    w = c["tags"].shape[2]                     # full set, key absent
    c["tags"][3, sets[3]] = ln["key"][3] ^ np.arange(1, w + 1, dtype=np.uint32)
    c["valid"][3, sets[3]] = True
    return (dataclasses.replace(caches, **{f: jnp.asarray(v) for f, v in c.items()}),
            CacheLine(**{f: jnp.asarray(v) for f, v in ln.items()}), sets)


@pytest.mark.parametrize("seed,n,s,w,d,fill", INLINE_CASES)
def test_insert_rows_inline_matches_scalar_vmap(seed, n, s, w, d, fill):
    """The inline upsert is pinned to the scalar ``insert`` semantics (vmap
    over nodes) — the kernels' source of truth is therefore the paper's
    single-node upsert, transitively.  Every case holds each branch of the
    upsert (``_plant_branches``) among random lanes."""
    rng = np.random.default_rng(seed)
    key_pool = rng.integers(0, 2**32, 8, dtype=np.uint32)
    caches, lines = _random_state(rng, n, s, w, d, key_pool, fill=fill)
    caches, lines, sets = _plant_branches(caches, lines, s)
    rows_c, rows_ev = insert_rows(caches, lines, jnp.int32(99))
    vmap_c, vmap_ev = jax.vmap(insert, in_axes=(0, 0, None))(
        caches, lines, jnp.int32(99)
    )
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(rows_c, f)), np.asarray(getattr(vmap_c, f)),
            err_msg=f"caches.{f}",
        )
    for f in ("key", "data_ts", "origin", "data", "valid", "dirty"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rows_ev, f)), np.asarray(getattr(vmap_ev, f)),
            err_msg=f"evicted.{f}",
        )
    assert int(rows_c.data_ts[0, sets[0], 0]) == 20           # newer wins
    assert int(rows_c.data_ts[1, sets[1], 0]) == 30           # stale kept
    for f in FIELDS:                                          # masked lane
        np.testing.assert_array_equal(np.asarray(getattr(rows_c, f))[2],
                                      np.asarray(getattr(caches, f))[2])
    assert bool(rows_ev.valid[3])                              # LRU victim


@pytest.mark.parametrize("seed", range(4))
def test_insert_rows_kernel_matches_inline_seeded(seed):
    """Hypothesis-free random sweep (the container has no hypothesis):
    divisor and non-divisor node counts, mixed geometries, all backends."""
    rng = np.random.default_rng(seed)
    for n, s, w in ((8, 4, 2), (13, 2, 4), (5, 8, 1), (16, 4, 4)):
        key_pool = rng.integers(0, 2**32, 6, dtype=np.uint32)
        caches, lines = _random_state(rng, n, s, w, 4, key_pool)
        _assert_same_upsert(caches, lines, jnp.int32(99))


def test_insert_rows_kernel_prime_node_count():
    """N=13 has no divisor ≤ N_BLOCK except 1: the wrapper must fall back
    to single-node blocks and stay bit-identical."""
    rng = np.random.default_rng(7)
    key_pool = rng.integers(0, 2**32, 6, dtype=np.uint32)
    caches, lines = _random_state(rng, 13, 4, 2, 4, key_pool)
    _assert_same_upsert(caches, lines, jnp.int32(99))


def test_insert_rows_kernel_stale_and_update_branches():
    """One node upserts a PRESENT key with a newer timestamp (in-place
    overwrite), one with an older timestamp (stale no-op), one lane is
    masked dead — the three branches of the soft-coherence gate — on every
    backend."""
    caches = empty_cache(2, 2, 2, jnp.float32, batch=(3,))
    keys = jnp.asarray([5, 7, 9], jnp.uint32)  # sets 1, 1, 1
    caches = dataclasses.replace(
        caches,
        tags=caches.tags.at[:, 1, 0].set(keys),
        valid=caches.valid.at[:, 1, 0].set(True),
        data_ts=caches.data_ts.at[:, 1, 0].set(10),
        last_use=caches.last_use.at[:, 1, 0].set(3),
    )
    lines = CacheLine(
        key=keys,
        data_ts=jnp.asarray([20, 10, 20], jnp.int32),  # newer, stale, dead
        origin=jnp.asarray([0, 1, 2], jnp.int32),
        data=jnp.full((3, 2), 4.0, jnp.float32),
        valid=jnp.asarray([True, True, False]),
        dirty=jnp.asarray([True, False, False]),
    )
    for be in (None,) + KERNEL_BACKENDS:
        new_c, _ = insert_rows(caches, lines, jnp.int32(42), backend=be)
        # node 0: strictly newer ⇒ in-place overwrite, all stamps refreshed
        assert int(new_c.data_ts[0, 1, 0]) == 20, be
        assert int(new_c.ins_ts[0, 1, 0]) == 42, be
        assert int(new_c.last_use[0, 1, 0]) == 42, be
        assert bool(new_c.dirty[0, 1, 0]), be
        # node 1: equal timestamp ⇒ stale, nothing moves
        assert int(new_c.data_ts[1, 1, 0]) == 10, be
        assert int(new_c.last_use[1, 1, 0]) == 3, be
        # node 2: dead lane ⇒ nothing moves anywhere in that cache
        np.testing.assert_array_equal(
            np.asarray(new_c.data_ts[2]), np.asarray(caches.data_ts[2]),
            err_msg=str(be),
        )
