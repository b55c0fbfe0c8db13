"""What the LFM2 cell's limits on the norms and the loss are held against.

The fp8 control moves ``grad_rel_diff`` (PERF.md section 4) and hardly
``grad_norm_gap``, ``delta_norm_gap`` and ``loss_gap``, so their limits have
no reading of the control above them. The readings above them are these
faults, planted in a reading that is otherwise the reference's own (every
other number reads 0, the first gradient's distance among them): each reads
what its definition gives at any size, and ``compare.judge`` under the CELL'S
OWN limits (not the rehearsal's) refuses it by that number alone. The
reading holds both mixers, a dense and a sparse layer and the tied head.
"""
import copy

import pytest

from harness import compare, loader, train_reference

CELL = "lfm2_a2b_train_s8192"


@pytest.fixture(scope="module")
def reading():
    """The reference's first steps at the rehearsal's size, and the limits of
    the cell as the chip runs it."""
    cell = loader.resolve_cell(CELL, True)
    config = loader.load_json("configs", cell["config"])
    traffic = loader.load_json("traffic", cell["traffic"])
    ref = loader.load_module("references", config["family"])
    opt = train_reference.effective_optimizer(config, traffic)
    plain = train_reference.first_steps(ref, config, opt, ref.init(config, 5),
                                        ref.batches(config, traffic, 5))
    return plain, loader.resolve_cell(CELL, False)["limits"]


def _largest(norms):
    return max(norms, key=norms.get)


def _the_tied_leaf_without_the_heads_part(program):
    # the embedding's gradient is the sum of the look-up's and the head's: a
    # tie that is not one loses a part of it (here: all of it)
    program["grad_norms"]["embed.w"] = 0.0


def _the_tied_leaf_counted_twice(program):
    program["grad_norms"]["embed.w"] *= 2.0


def _a_leaf_without_gradient(program):
    program["grad_norms"][_largest(program["grad_norms"])] = 0.0


def _a_state_left_unchanged(program):
    program["delta_norms"] = dict.fromkeys(program["delta_norms"], 0.0)


def _half_the_positions_in_the_loss(program):
    program["losses"] = [v / 2.0 for v in program["losses"]]


FAULTS = {
    "the_tied_leaf_without_the_heads_part": (
        _the_tied_leaf_without_the_heads_part, "grad_norm_gap", 1.0),
    "the_tied_leaf_counted_twice": (_the_tied_leaf_counted_twice, "grad_norm_gap", 1.0),
    "a_leaf_without_gradient": (_a_leaf_without_gradient, "grad_norm_gap", 1.0),
    "a_state_left_unchanged": (_a_state_left_unchanged, "delta_norm_gap", 1.0),
    "half_the_positions_in_the_loss": (_half_the_positions_in_the_loss, "loss_gap", 0.5),
}


def test_the_references_own_reading_is_correct_under_the_cells_limits(reading):
    plain, limits = reading
    assert {"embed.w", "l0.conv.w", "l1.q.w", "l0.gate.w", "l1.experts.gate"} <= set(
        plain["grad_norms"])
    rows = compare.judge(compare.training_numbers(plain, dict(plain, grad_rel_diff=0.0)),
                         limits)
    assert all(r["ok"] and r["value"] == 0.0 for r in rows if r["limit"] is not None)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_above_the_cells_limit_and_is_refused_by_it_alone(
        reading, fault):
    plain, limits = reading
    plant, number, reads = FAULTS[fault]
    program = copy.deepcopy(plain)
    plant(program)
    rows = compare.judge(
        compare.training_numbers(program, dict(plain, grad_rel_diff=0.0)), limits)
    failed = {r["compared"].split(".")[0] for r in rows if not r["ok"]}
    assert failed == {number}
    worst = max(r["value"] for r in rows if r["compared"].split(".")[0] == number)
    assert worst == pytest.approx(reads, rel=1e-6)
    # the limit stands between the sound runs' largest on the chip (PERF.md
    # section 4) and this reading
    assert limits[number] < reads / 10
