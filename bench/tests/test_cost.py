"""The fused arnoldi_step's cost model (bench/cost.py) against a count by
hand from the kernel's block shapes, and its roofline share on a trace
written by hand."""
import pytest

from bench import cost

CONFIG = {"family_params": {"nx": 16, "ny": 128},
          "krylov": {"m": 3, "k": 2}}
TRAFFIC = {"chains": 2}


def test_bytes_match_a_count_by_hand():
    # nx 16 in tiles of 8 rows (nt = 2), ny 128, basis 4 rows, C 2 rows.
    # Per chain the grid runs 5 phases x 2 tiles and the tile index
    # alternates every step, so each tile-indexed block is copied 10 times;
    # of those, the basis (4 planes) and C (2) are in HBM, the rest in VMEM.
    plane = 8 * 128
    assert cost.arnoldi_step_bytes(2, 3, 2, 16, 128, block_rows=8) \
        == 2 * 4 * 10 * (4 + 2) * plane


def test_one_tile_reads_the_basis_once_per_chain():
    # one tile: every block once per chain; a fresh call's zero row of C
    # lives in VMEM, so only the basis (5 rows) crosses HBM
    assert cost.arnoldi_step_bytes(3, 4, 0, 8, 128) == 3 * 4 * 5 * 8 * 128


def test_blocks_follow_the_index_maps():
    """A clamped halo index repeats: at 2 tiles the upper halo is block 0
    throughout, so it is copied once per chain; at 4 tiles it runs 0, 0, 1,
    2 in each of the 5 phases, 3 copies a phase."""
    two = {name: index for name, _, index, _ in cost.blocks(4, 2, 16, 128, 8)}
    four = {name: index for name, _, index, _ in cost.blocks(4, 2, 32, 128,
                                                             8)}
    assert cost.copies(two["vin_up"], 2, 3) == 3
    assert cost.copies(four["inv_diag_up"], 4, 1) == 15
    assert cost.copies(four["v_basis"], 4, 2) == 2 * 5 * 4


def test_shape_of_names_the_program():
    assert cost.shape_of("jit(_deflated_cycle)/vmap(skr/arnoldi)/x", 40,
                         15) == (25, 15)
    assert cost.shape_of("jit(_fresh_cycle)/vmap(skr/arnoldi)/x", 40,
                         15) == (40, 0)
    assert cost.shape_of("jit(other)/x", 40, 15) is None


@pytest.mark.parametrize("slow", [1.0, 2.0])
def test_roofline_of_calls_at_exactly_the_bandwidth(slow):
    """Kernel events that take exactly bytes / bandwidth read 100 %, never
    more; twice as long reads 50 %. Other ops and calls outside the slice
    do not count."""
    bw = cost.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    fresh = cost.arnoldi_step_bytes(2, 3, 0, 16, 128)
    defl = cost.arnoldi_step_bytes(2, 1, 2, 16, 128)
    events, t = [], 1000.0
    for name, op, nb in [("arnoldi_step.3", "jit(_fresh_cycle)/a", fresh),
                         ("fusion.1", "jit(_fresh_cycle)/b", 10 ** 9),
                         ("arnoldi_step.7", "jit(_deflated_cycle)/a", defl)]:
        dur = slow * nb / bw * 1e9
        events.append((name, t, t + dur, op))
        t += dur + 50.0
    events.append(("arnoldi_step.3", t + 1e9, t + 2e9, "jit(_fresh_cycle)/a"))
    got = cost.roofline(events, 0.0, t + 1.0, CONFIG, TRAFFIC, "TPU v5 lite")
    assert got["calls"] == 2
    assert got["value"] == pytest.approx(100.0 / slow, rel=1e-9)
    assert got["value"] <= 100.0 + 1e-9


def test_no_call_reads_nothing_and_an_unknown_chip_is_an_error():
    assert cost.roofline([("fusion.1", 0.0, 1.0, "jit(_fresh_cycle)")], 0.0,
                         2.0, CONFIG, TRAFFIC, "TPU v5 lite") is None
    with pytest.raises(KeyError):
        cost.peaks("TPU v9")
