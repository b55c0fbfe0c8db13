"""The comparison that decides ``correct`` for a training cell.

Every number compared is printed beside its limit. Norms are compared by the
worst leaf: the gap between the program's norm and the reference's (not the
norm of their difference), against the reference's norm of that leaf or of
the median leaf, whichever is larger, since some gradients are all but zero.
The median is taken over the leaves whose reference norm is not zero.

The first gradient's distance from the reference's is read twice. All leaves
together (``grad_rel_diff``) weights a leaf by its squared norm: sound where
the norm is spread over the leaves, one easy part where a single leaf holds
it (ResNet-50's classifier holds 98.5 % once the residual branches start at
0.001). Leaf by leaf (``grad_leaf_diff``, the mean over leaves, and
``grad_leaf_diff_worst``) weights every leaf the same. A cell's ``limits``
say which numbers it is held to; a limit that names no number is a fault.
"""
from __future__ import annotations

import math
import statistics


def worst_leaf_gap(program, reference):
    """(worst gap, its leaf) over the leaves of two {name: norm} dicts."""
    if set(program) != set(reference):
        raise ValueError("leaves differ: %s" % sorted(set(program) ^ set(reference))[:4])
    nonzero = [v for v in reference.values() if v > 0.0]
    floor = statistics.median(nonzero) if nonzero else 0.0
    worst, where = 0.0, None
    for name, ref in reference.items():
        got = program[name]
        if not math.isfinite(got):
            return math.inf, name
        gap = abs(got - ref) / max(ref, floor, 1e-30)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def leaf_diffs(diff_norms, reference):
    """{leaf: |program - reference| / |reference|} of one gradient, every leaf
    against its own reference norm. A leaf whose reference norm is under a
    thousandth of the median leaf's is left out: it is rounding in float32
    too (a bias in front of a batch norm has no gradient), and the gap of
    norms holds it against the median leaf."""
    nonzero = [v for v in reference.values() if v > 0.0]
    floor = statistics.median(nonzero) / 1000.0 if nonzero else 0.0
    out = {}
    for name, ref in reference.items():
        if ref > floor:
            d = diff_norms[name]
            out[name] = d / ref if math.isfinite(d) else math.inf
    return out


def training_numbers(program, reference):
    """[(name, value, detail)] for two readings of the first steps, each
    ``{"losses": [..], "grad_norms": {..}, "delta_norms": {..}}``."""
    out = []
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        gap = abs(a - b) / abs(b) if math.isfinite(a) else math.inf
        out.append(("loss_gap.step%d" % (i + 1), gap,
                    "program %.6f reference %.6f" % (a, b)))
    g, where = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    out.append(("grad_norm_gap", g, "worst leaf %s" % where))
    if "grad_rel_diff" in reference:
        out.append(("grad_rel_diff", reference["grad_rel_diff"],
                    "first gradient, all leaves together: |program - reference| / |reference|"))
    per_leaf = None
    if "grad_diff_norms" in reference:
        per_leaf = leaf_diffs(reference["grad_diff_norms"], reference["grad_norms"])
    if per_leaf:
        where = max(per_leaf, key=per_leaf.get)
        out.append(("grad_leaf_diff", statistics.fmean(per_leaf.values()),
                    "first gradient, mean over %d leaves of |program - reference| / "
                    "|reference| of the leaf" % len(per_leaf)))
        out.append(("grad_leaf_diff_worst", per_leaf[where], "worst leaf %s" % where))
    d, where = worst_leaf_gap(program["delta_norms"], reference["delta_norms"])
    out.append(("delta_norm_gap", d, "worst leaf %s" % where))
    return out


NEEDED = ("loss_gap", "grad_norm_gap", "delta_norm_gap")
DISTANCES = ("grad_rel_diff", "grad_leaf_diff")


def judge(numbers, limits):
    """Rows of name, value, limit, ok, for the numbers that the cell's limits
    name. A limit that names no number, or a cell with no limit on the losses,
    the norms or any distance of the first gradient, is a fault of the cell's
    file, not a pass. A number the cell is not held to has the limit null."""
    keys = {name.split(".")[0] for name, _, _ in numbers}
    for key in limits:
        if key not in keys:
            raise KeyError("the cell's limit %r names no number compared" % key)
    for key in NEEDED:
        if key not in limits:
            raise KeyError("the cell gives no limit for %r" % key)
    if not any(key in limits for key in DISTANCES):
        raise KeyError("the cell gives no limit for any of %s" % (DISTANCES,))
    rows = []
    for name, value, detail in numbers:
        limit = limits.get(name.split(".")[0])
        rows.append({"compared": name, "value": value, "limit": limit,
                     "ok": bool(limit is None or value <= limit), "detail": detail})
    return rows
