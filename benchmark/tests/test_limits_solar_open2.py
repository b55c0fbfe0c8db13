"""What the Solar Open 2 cell's limits are held against.

On the chip (PERF.md section 4) the fp8 control moves ``grad_rel_diff`` eleven
times and ``grad_norm_gap`` 3.5 to 4.6 times past the largest of seventeen sound
runs, and both faults planted in the delta rule move them farther;
``delta_norm_gap`` reads under three times the sound runs' largest under the
control and ``loss_gap`` reads inside them, so for those two the upper readings
are the planted ones below. ``SOUND`` and ``REFUSED`` are the chip's own readings, every one made so
far: ``compare.judge`` under the CELL'S OWN limits passes each sound run with
half of every limit to spare and refuses the control and each fault. Beside
them, planted in a reading that is otherwise the reference's own (every other
number reads 0, the first gradient's distance among them), are the faults that
read what their definition gives at any size: each is refused by its number
alone. The rehearsal of the cell on the CPU is ``correct``, and a reference
with one of its layers written otherwise (every chunk opening on a zero state,
beta not doubled, the gate before the norm, a head's channels decaying by their
mean, no gate on the attention) is refused by the cell's own limit on the first
gradient's distance.
"""
import copy

import pytest

from harness import compare, loader, train_reference

CELL = "solar_open2_train_s8192"


# run.py's `compared` lines of every sound run of the program on the chip (my
# chip runs, PR 47, calls 1, 2, 4 and 5: seventeen seeds): seed -> the largest
# loss_gap of the three steps, grad_norm_gap, grad_rel_diff, delta_norm_gap
SOUND = {
    2147501001: (0.002635, 0.003130, 0.04251, 0.0005849),
    2147501003: (0.0009907, 0.002001, 0.04595, 0.0005740),
    2147501007: (0.0006139, 0.002274, 0.04012, 0.0005848),
    2147501011: (0.002839, 0.003009, 0.03866, 0.0006164),
    918273681: (0.002602, 0.002384, 0.03892, 0.0006011),
    777021: (0.002399, 0.002983, 0.04104, 0.0005178),
    2147502003: (0.002709, 0.002294, 0.04104, 0.0005460),
    2147502101: (0.0006362, 0.005197, 0.04428, 0.001114),  # calls 2 and 4, to the digit
    2147503001: (0.001436, 0.002309, 0.04068, 0.0005537),
    2147503003: (0.0004492, 0.001958, 0.04093, 0.0005429),
    2147503007: (0.002583, 0.002732, 0.04109, 0.0007305),
    2147503011: (0.002464, 0.002303, 0.03976, 0.0005772),
    31337: (0.001229, 0.003121, 0.04230, 0.001116),
    777123: (0.002651, 0.002022, 0.04125, 0.0005610),
    918274101: (0.001191, 0.003242, 0.04213, 0.0005689),
    2147504001: (0.001974, 0.002433, 0.03979, 0.0006714),  # call 5: the committed files
    2147504003: (0.0009977, 0.002294, 0.04272, 0.0007516),
}
# tools/calibrate_planted.py on the chip (call 1, seed 2147501101, under the
# draft's limits; call 4, seed 2147503001, under the limits that stand, where
# the tool's own verdict was `correct` false by these numbers): the float32
# reference in fp8, or with a fault planted in its delta rule, held against the
# sound reference as a program is; the same four numbers, then the numbers that
# refuse it under the cell's limits
REFUSED = {
    "fp8@2147501101": ((0.0006653, 0.02412, 0.5027, 0.002685),
                       {"grad_norm_gap", "grad_rel_diff"}),
    "carry_dropped@2147501101": ((0.0009301, 0.08268, 0.6438, 0.002302),
                                 {"grad_norm_gap", "grad_rel_diff"}),
    "beta_not_doubled@2147501101": ((0.0005251, 0.2293, 0.3576, 0.004803),
                                    {"grad_norm_gap", "grad_rel_diff", "delta_norm_gap"}),
    "fp8@2147503001": ((0.0002519, 0.01814, 0.5104, 0.002739),
                       {"grad_norm_gap", "grad_rel_diff"}),
    "carry_dropped@2147503001": ((0.0008187, 0.08850, 0.6617, 0.002799),
                                 {"grad_norm_gap", "grad_rel_diff"}),
    "beta_not_doubled@2147503001": ((0.0001764, 0.2340, 0.3684, 0.003431),
                                    {"grad_norm_gap", "grad_rel_diff"}),
}


def _as_numbers(reading):
    names = ("loss_gap.step1", "grad_norm_gap", "grad_rel_diff", "delta_norm_gap")
    return [(name, value, "") for name, value in zip(names, reading)]


def _cell_limits():
    return loader.resolve_cell(CELL, False)["limits"]


@pytest.mark.parametrize("seed", sorted(SOUND))
def test_a_sound_run_on_the_chip_passes_with_half_of_every_limit_to_spare(seed):
    rows = compare.judge(_as_numbers(SOUND[seed]), _cell_limits())
    assert all(r["ok"] and 2 * r["value"] <= r["limit"] for r in rows), rows


@pytest.mark.parametrize("which", sorted(REFUSED))
def test_the_chips_reading_of_the_control_or_a_fault_is_refused(which):
    reading, refused_by = REFUSED[which]
    rows = compare.judge(_as_numbers(reading), _cell_limits())
    assert {r["compared"].split(".")[0] for r in rows if not r["ok"]} == refused_by
    # by the first gradient's distance at three times its limit, and by the gap
    # of norms at one and a half times its limit or more
    by = {r["compared"]: r["value"] / r["limit"] for r in rows}
    assert by["grad_rel_diff"] > 2.9 and by["grad_norm_gap"] >= 1.5


def _follow(config, traffic, ref, **kwargs):
    opt = train_reference.effective_optimizer(config, traffic)
    return train_reference.first_steps(ref, config, opt, ref.init(config, 5),
                                       ref.batches(config, traffic, 5), **kwargs)


@pytest.fixture(scope="module")
def files():
    cell = loader.resolve_cell(CELL, True)
    config = loader.load_json("configs", cell["config"])
    assert (cell["config"], cell["traffic"]) == ("rehearse_solar_open2",
                                                 "rehearse_train_tokens_s44")
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


def _a_leaf_counted_twice(program):
    program["grad_norms"][_largest(program["grad_norms"])] *= 2.0


def _a_state_left_unchanged(program):
    program["delta_norms"] = dict.fromkeys(program["delta_norms"], 0.0)


def _half_the_positions_in_the_loss(program):
    program["losses"] = [v / 2.0 for v in program["losses"]]


FAULTS = {
    "a_leaf_without_gradient": (_a_leaf_without_gradient, "grad_norm_gap", 1.0),
    "a_leaf_counted_twice": (_a_leaf_counted_twice, "grad_norm_gap", 1.0),
    "a_state_left_unchanged": (_a_state_left_unchanged, "delta_norm_gap", 1.0),
    "half_the_positions_in_the_loss": (_half_the_positions_in_the_loss, "loss_gap", 0.5),
}


def _numbers(program, plain, distance=0.0):
    plain = {k: v for k, v in plain.items() if k != "first_gradient"}
    return compare.training_numbers(program, dict(plain, grad_rel_diff=distance))


def test_the_references_own_reading_is_correct_under_the_cells_limits(reading):
    plain, limits = reading
    assert {"embed.w", "head.w", "l0.gate.w", "l0.kv.w", "l1.qkv.w", "l1.conv.w",
            "l1.A_log", "l1.dt_bias", "l1.gb.bias", "l2.o_norm.g", "l2.experts.down",
            "l2.shared.up.w"} <= set(plain["grad_norms"])
    rows = compare.judge(_numbers(plain, plain), limits)
    assert all(r["ok"] and r["value"] == 0.0 for r in rows if r["limit"] is not None)
    assert set(limits) == {"loss_gap", "grad_norm_gap", "grad_rel_diff", "delta_norm_gap"}


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
    assert limits[number] < reads / 5


@pytest.mark.parametrize("fault", ["carry_dropped", "beta_not_doubled", "gate_before_norm",
                                   "decay_head_mean", "no_gqa_gate"])
def test_a_layer_computed_otherwise_is_refused_by_the_cells_limits(files, reading, fault):
    """The float32 reference with one layer written otherwise, held against the
    sound reference as a program is: its first gradient lies farther off than
    the cell's limit admits."""
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
    assert rel > 2 * limits["grad_rel_diff"], rel


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
