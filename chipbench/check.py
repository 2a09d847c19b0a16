"""The comparison that decides ``correct``: the program's first steps against
the plain reference's, each number beside a limit of its own (set from
readings, see PERF.md section 2 and ``limits/<workload>.json``).

Of a net with BatchNorm under SGD three numbers are held. Two are gaps of
NORMS over the trainable leaves (|program's norm - reference's norm|, against
the reference's norm of that leaf or of the median leaf, whichever is larger,
since some gradients are all but zero), taken at the median leaf: the first gradient and the change
after the last followed step. They catch rows left out and a state left
unchanged; they cannot tell bfloat16 from float8, because rounding moves a
ReLU network's backward pass by mask flips, about as much at 7 bits of
mantissa as at 3. The third is the norm of the DIFFERENCE of the running
statistics' first change, a forward quantity that rounding moves in
proportion: it is the one the lower-precision control fails. Losses and the
worst leaves are worked out and recorded, and held to no limit (PERF.md).

Under Adam the norm of the first update is lr * sqrt(numel) whatever the
gradient, so a gap of norms is blind to it, and a net without running
statistics has no third number. Two numbers of DIRECTION see both, from the
two sides' leaves side by side: the share of a leaf's elements whose first
update has the opposite sign (``sign1``), and the norm of the DIFFERENCE of
the two sides' change after the last followed step over the reference's norm
of it (``ddiff``), each at the median leaf. Every number is worked out where
its inputs exist and recorded in every run; a cell's limits file names the
ones it holds.
"""
import statistics

import numpy as np

DEAD_GRADIENT = 1e-3  # of the median leaf's: such a leaf moves by round-off


def leaf_gaps(prog, ref, keep=None):
    med = statistics.median(ref)
    gaps = [abs(p - r) / max(r, med) for p, r in zip(prog, ref)]
    if keep is not None:
        gaps = [g for g, k in zip(gaps, keep) if k]
    return gaps


def numbers(prog, ref):
    """{name: value} of everything compared and everything recorded."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"])):
        out["loss%d" % (i + 1)] = abs(p - r) / abs(r)
    med = statistics.median(ref["grad1"])
    live = [g >= DEAD_GRADIENT * med for g in ref["grad1"]]
    g = leaf_gaps(prog["grad1"], ref["grad1"])
    d = leaf_gaps(prog["dparam"], ref["dparam"], live)
    out["grad1_worst_leaf"] = max(g)
    out["dparam_worst_leaf"] = max(d)
    out["grad1_median_leaf"] = statistics.median(g)
    out["dparam_median_leaf"] = statistics.median(d)
    if ref["state1"]:
        out["state1_median_leaf"] = statistics.median(
            diff_gaps(prog["state1"], ref["state1"]))
    out["sign1_median_leaf"] = statistics.median(
        sign_flips(prog["update1"], ref["update1"]))
    dd = diff_gaps(prog["change"], ref["change"], live)
    out["ddiff_median_leaf"] = statistics.median(dd)
    out["ddiff_worst_leaf"] = max(dd)
    return out


def sign_flips(prog, ref):
    """Per trainable leaf: the share of the elements the reference moves in
    its first update that the program moves the other way, or not at all."""
    out = []
    for p, r in zip(prog, ref):
        moved = r != 0
        n = int(moved.sum())
        out.append(float((np.sign(p) != np.sign(r))[moved].sum()) / n if n else 0.0)
    return out


def diff_gaps(prog, ref, keep=None):
    """Per leaf: the norm of the DIFFERENCE of the two sides' change over the
    reference's norm of it (of that leaf or of the median leaf, whichever is
    larger). Of the running statistics (a BatchNorm's mean or variance) these
    are forward quantities, means over the whole batch: rounding moves them
    in proportion to its size, where it moves a gradient's direction by as
    much at 8 bits of mantissa as at 3 (PERF.md section 2)."""
    norm = lambda v: float(np.sum(np.square(v, dtype=np.float64))) ** 0.5  # noqa: E731
    norms = [norm(r) for r in ref]
    med = statistics.median(norms)
    gaps = [norm(p - r) / max(n, med) for p, r, n in zip(prog, ref, norms)]
    if keep is not None:
        gaps = [g for g, k in zip(gaps, keep) if k]
    return gaps


def worst_leaves(prog, ref, k=3):
    """For a look by hand: the k widest gaps of each kind as
    (leaf, gap, program's norm, reference's norm)."""
    out = {}
    for name in ("grad1", "dparam"):
        gaps = leaf_gaps(prog[name], ref[name])
        top = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:k]
        out[name] = [(i, gaps[i], prog[name][i], ref[name][i]) for i in top]
        out[name + "_median_norm"] = statistics.median(ref[name])
    return out


def judge(nums, limits):
    """[(name, value, limit)] for every number that has a limit, and whether
    all hold. A number that is not finite fails."""
    rows = [(k, nums[k], limits[k]) for k in limits]
    ok = all(v == v and v <= lim for _, v, lim in rows)
    return rows, ok
