"""Statistics and the comparison's arithmetic."""
import math

import pytest

from harness import compare, stats, trace_reduce


def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0
    assert stats.iqr_share([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)


def test_worst_leaf_gap_is_a_gap_of_norms_over_leaf_or_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9, "zero": 0.0, "zero2": 0.0}
    got = {"a": 1.1, "b": 2.0, "c": 0.5, "zero": 0.0, "zero2": 0.0}
    gap, leaf = compare.worst_leaf_gap(got, ref)
    assert leaf == "c" and gap == pytest.approx(0.5 / 1.0)  # against the median leaf
    assert compare.worst_leaf_gap(dict(got, c=math.nan), ref)[0] == math.inf
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"a": 1.0}, ref)


def test_judge_holds_a_cell_to_the_numbers_its_limits_name():
    numbers = [("loss_gap.step1", 0.001, ""), ("grad_norm_gap", 0.5, ""),
               ("grad_rel_diff", 0.01, ""), ("grad_leaf_diff", 0.2, ""),
               ("delta_norm_gap", 0.1, "")]
    limits = {"loss_gap": 0.01, "grad_norm_gap": 0.1, "grad_rel_diff": 0.02,
              "delta_norm_gap": 0.3}
    rows = compare.judge(numbers, limits)
    assert [r["ok"] for r in rows] == [True, False, True, True, True]
    assert [r["limit"] for r in rows] == [0.01, 0.1, 0.02, None, 0.3]  # not held to it
    with pytest.raises(KeyError):  # a limit that names no number compared
        compare.judge(numbers, dict(limits, grad_direction=0.1))
    for key in ("loss_gap", "grad_norm_gap", "delta_norm_gap", "grad_rel_diff"):
        with pytest.raises(KeyError):  # the losses, both norms and one distance at least
            compare.judge(numbers, {k: v for k, v in limits.items() if k != key})


def test_leaf_by_leaf_every_leaf_weighs_the_same():
    ref = {"big": 100.0, "mid": 1.0, "small": 0.01, "rounding": 1e-9, "zero": 0.0}
    diff = {"big": 1.0, "mid": 0.01, "small": 0.01, "rounding": 1e-6, "zero": 0.0}
    per_leaf = compare.leaf_diffs(diff, ref)
    # a thousandth of the median leaf is rounding in float32 too: left out
    assert per_leaf == {"big": pytest.approx(0.01), "mid": pytest.approx(0.01),
                        "small": pytest.approx(1.0)}
    numbers = dict((n, v) for n, v, _ in compare.training_numbers(
        {"losses": [1.0], "grad_norms": ref, "delta_norms": ref},
        {"losses": [1.0], "grad_norms": ref, "delta_norms": ref,
         "grad_rel_diff": 0.01, "grad_diff_norms": diff}))
    # the small leaf's gradient is wholly wrong: the norm-weighted distance
    # hardly moves, the mean over leaves and the worst leaf do
    assert numbers["grad_rel_diff"] == 0.01
    assert numbers["grad_leaf_diff"] == pytest.approx(0.34)
    assert numbers["grad_leaf_diff_worst"] == pytest.approx(1.0)
    assert compare.leaf_diffs(dict(diff, mid=math.nan), ref)["mid"] == math.inf


def test_reduce_planes_on_a_trace_worked_by_hand():
    ms = 1_000_000
    planes = {
        "devices": {"/device:TPU:0": [("fusion.1", 0, 4 * ms), ("fusion.2", 3 * ms, 6 * ms),
                                      ("copy", 8 * ms, 9 * ms), ("late", 20 * ms, 30 * ms)]},
        "spans": [("bench.trace_window", 0, 10 * ms), ("bench.wait", 5 * ms, 9 * ms),
                  ("bench.dispatch_step", 8 * ms + 500_000, 10 * ms)],
    }
    out = trace_reduce.reduce_planes(planes)
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx(0.007)  # union of 0-6 and 8-9, 'late' is outside
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert out["idle_gaps"][0] == ["bench.wait", pytest.approx(0.002)]
    assert out["idle_gaps"][1] == ["bench.dispatch_step", pytest.approx(0.001)]
    assert trace_reduce.reduce_planes({"devices": {}, "spans": []}) is None


def test_reduce_the_small_trace_recorded_on_the_chip():
    """``tests/data/small.xplane.pb``: twelve launches of one small program on
    a TPU v5e with 5 ms of sleep between them (my chip run, PR 24, call 1)."""
    import os

    path = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
    assert os.path.getsize(path) < 1_000_000
    out = trace_reduce.reduce_planes(trace_reduce.read_planes(path))
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.078474005)
    assert out["busy_s"] == pytest.approx(0.003971311)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.9494, abs=1e-4)
    assert out["device_ops"][0] == ["fusion bf16[2048,2048]", pytest.approx(0.001000615)]
    assert out["kernel_s"] == 0.0
    assert [g[0] for g in out["idle_gaps"]] == ["bench.sleep"] * 5
    assert out["idle_gaps"][0][1] == pytest.approx(0.007484144)
