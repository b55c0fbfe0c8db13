"""What the Granite cell's limits are held against.

The fp8 control moves ``grad_rel_diff`` and ``grad_norm_gap`` (PERF.md section
4) and hardly ``delta_norm_gap`` and ``loss_gap``, so those limits have no
reading of the control above them. The readings above them are these faults,
planted in a reading that is otherwise the reference's own (every other
number reads 0, the first gradient's distance among them): each reads what its
definition gives at any size, and ``compare.judge`` under the CELL'S OWN limits
(not the rehearsal's) refuses it by that number alone. Beside them the
rehearsal of the cell on the CPU is ``correct``, and a reference whose chunks
each open on a zero state, or whose gate comes after its norm, is refused by
the cell's own limit on the first gradient's distance.
"""
import copy

import pytest

from harness import compare, loader, train_reference

CELL = "granite4_h_micro_train_s8192"


def _follow(config, traffic, ref, **kwargs):
    opt = train_reference.effective_optimizer(config, traffic)
    return train_reference.first_steps(ref, config, opt, ref.init(config, 5),
                                       ref.batches(config, traffic, 5), **kwargs)


@pytest.fixture(scope="module")
def files():
    cell = loader.resolve_cell(CELL, True)
    config = loader.load_json("configs", cell["config"])
    assert (cell["config"], cell["traffic"]) == ("rehearse_granite_hybrid",
                                                 "rehearse_train_tokens")
    return (config, loader.load_json("traffic", cell["traffic"]),
            loader.load_module("references", config["family"]))


@pytest.fixture(scope="module")
def reading(files):
    """The reference's first steps at the rehearsal's size (its first gradient
    kept), and the limits of the cell as the chip runs it."""
    plain = _follow(*files, keep_gradient=True)
    return plain, loader.resolve_cell(CELL, False)["limits"]


def _largest(norms):
    return max(norms, key=norms.get)


def _a_leaf_without_gradient(program):
    program["grad_norms"][_largest(program["grad_norms"])] = 0.0


def _the_tied_leaf_counted_once(program):
    # the embedding's gradient without the head's share: the look-up's rows alone
    program["grad_norms"]["embed.w"] *= 0.5


def _a_state_left_unchanged(program):
    program["delta_norms"] = dict.fromkeys(program["delta_norms"], 0.0)


def _half_the_positions_in_the_loss(program):
    program["losses"] = [v / 2.0 for v in program["losses"]]


FAULTS = {
    "a_leaf_without_gradient": (_a_leaf_without_gradient, "grad_norm_gap", 1.0),
    "the_tied_leaf_counted_once": (_the_tied_leaf_counted_once, "grad_norm_gap", 0.5),
    "a_state_left_unchanged": (_a_state_left_unchanged, "delta_norm_gap", 1.0),
    "half_the_positions_in_the_loss": (_half_the_positions_in_the_loss, "loss_gap", 0.5),
}


def _numbers(program, plain, distance=0.0):
    plain = {k: v for k, v in plain.items() if k != "first_gradient"}
    return compare.training_numbers(program, dict(plain, grad_rel_diff=distance))


def test_the_references_own_reading_is_correct_under_the_cells_limits(reading):
    plain, limits = reading
    assert {"embed.w", "l0.in.w", "l0.conv.w", "l0.A_log", "l0.dt_bias", "l0.D",
            "l1.kv.w", "l2.gate_norm.g", "l2.down.w"} <= set(plain["grad_norms"])
    assert "head.w" not in plain["grad_norms"]  # tied: one leaf
    rows = compare.judge(_numbers(plain, plain), limits)
    assert all(r["ok"] and r["value"] == 0.0 for r in rows if r["limit"] is not None)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_reads_above_the_cells_limit_and_is_refused_by_it_alone(
        reading, fault):
    plain, limits = reading
    plant, number, reads = FAULTS[fault]
    program = copy.deepcopy({k: v for k, v in plain.items() if k != "first_gradient"})
    plant(program)
    rows = compare.judge(_numbers(program, plain), limits)
    failed = {r["compared"].split(".")[0] for r in rows if not r["ok"]}
    assert failed == {number}
    worst = max(r["value"] for r in rows if r["compared"].split(".")[0] == number)
    assert worst == pytest.approx(reads, rel=1e-6)
    # the limit stands between the sound runs' largest on the chip (PERF.md
    # section 4) and this reading
    assert limits[number] < reads / 10


@pytest.mark.parametrize("fault", ["carry_dropped", "gate_after_norm"])
def test_a_mixer_computed_otherwise_is_refused_by_the_cells_limits(files, reading, fault):
    """The float32 reference with every chunk opening on a zero state (the
    carry between chunks dropped), or with the gate applied after the norm,
    held against the sound reference as a program is: its first gradient lies
    farther off than the cell's limit admits."""
    config, traffic, ref = files
    plain, limits = reading
    low = _follow(config, traffic, ref, quant=fault, keep_gradient=True)
    rel, norms = train_reference.gradient_distance(low.pop("first_gradient"),
                                                   plain["first_gradient"])
    plain_numbers = {k: v for k, v in plain.items() if k != "first_gradient"}
    rows = compare.judge(compare.training_numbers(
        low, dict(plain_numbers, grad_rel_diff=rel, grad_diff_norms=norms)), limits)
    failed = {r["compared"].split(".")[0] for r in rows if not r["ok"]}
    print(fault, "reads", rel, "failed", sorted(failed))
    assert "grad_rel_diff" in failed, rows
    assert rel > limits["grad_rel_diff"], rel


def test_the_rehearsal_of_the_cell_is_correct(capsys):
    """``run.py --rehearse`` on the cell: exit 3 (never a result), ``correct``
    true, one launch a step, nothing compiled in the window."""
    import json

    import run as bench

    rc = bench.main(["--workload", CELL, "--seed", "2147483999", "--seconds", "2",
                     "--trace", "0", "--rehearse"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    result = json.loads(lines[-1])
    window = next(json.loads(l) for l in lines if '"phase": "window"' in l)
    assert rc == 3 and result["correct"] is True
    assert window["launches"] == window["steps"] and window["host_syncs"] == 0
    assert result["compared"]["compiles_in_window"]["value"] == 0
    assert all(m["value"] is None for m in result["metrics"].values())
