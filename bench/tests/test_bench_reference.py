"""The plain reference follows the program at a small size: every integer
row field and every state element equal, the float fields within the
limit, on both cells' configurations and several seeds."""
import numpy as np
import pytest

from harness import cells, check, control

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_program_matches_reference(name, seed):
    cell = cells.load_cell(name)
    cfg = cells.sim_config(cell, n_nodes=48)
    spec = cells.spec_with(cell, n_nodes=48)
    numbers, _, info = control.program_numbers(cfg, spec, cell, seed,
                                               extra_chunks=3)
    assert check.correct(numbers), numbers
    assert numbers["row_mismatch"] == 0 and numbers["state_mismatch"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """bfloat16 payload tables and modelled bytes in the program's place
    fail the comparison: the payload lanes and the float row fields."""
    cell = cells.load_cell(name)
    spec = cells.spec_with(cell, n_nodes=48)
    for seed in (1, 2, 3):
        numbers = control.control_numbers(cell, spec, seed, cell.ref_chunks[0])
        assert not check.correct(numbers), numbers
        assert numbers["state_mismatch"] > 0 and numbers["bad_lines"] > 0
        assert numbers["float_gap"] > check.LIMITS["float_gap"]


def test_reference_hash_and_payload_match_the_program():
    import jax.numpy as jnp

    from harness import reference as ref
    from repro.core import workload as wl
    from repro.utils.hashing import hash2_u32

    a = np.arange(0, 2**32, 2**32 // 4099, dtype=np.uint64).astype(np.uint32)
    b = a[::-1].copy()
    np.testing.assert_array_equal(ref.hash2(a, b), np.asarray(hash2_u32(a, b)))
    np.testing.assert_array_equal(
        ref.payload(a, 8).view(np.uint32),
        np.asarray(wl.payload_for(jnp.asarray(a), 8)).view(np.uint32))
