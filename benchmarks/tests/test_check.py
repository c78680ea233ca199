"""The arithmetic that decides ``correct``."""

import math

from benchmarks import check


def test_worst_leaf_gap_is_the_gap_of_norms_against_leaf_or_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gap, leaf = check.worst_leaf_gap(prog, ref)
    assert leaf == "a" and math.isclose(gap, 0.1, rel_tol=1e-9)
    # the all-but-zero leaf is measured against the median leaf
    prog["c"] = 0.5
    gap, leaf = check.worst_leaf_gap(prog, ref)
    assert leaf == "c" and math.isclose(gap, 0.5, rel_tol=1e-6)
    gap, leaf = check.worst_leaf_gap(prog, ref, skip=("c",))
    assert leaf == "a"


def test_a_leaf_left_unmoved_reads_one():
    gap, _ = check.worst_leaf_gap({"a": 0.0, "b": 1.0},
                                  {"a": 1.0, "b": 1.0})
    assert gap == 1.0


def test_nought_leaves_go_by_the_reference_gradient_not_by_name():
    grads = {"w": 1.0, "x": 0.5, "y": 2.0, "bk": 1e-8}
    assert check.nought_leaves(grads) == ["bk"]


def test_verdict_needs_every_number_under_its_limit():
    ok, compared = check.verdict({"a": 0.1, "b": 0.0}, {"a": 0.2, "b": 0})
    assert ok and compared["a"] == {"value": 0.1, "limit": 0.2}
    assert not check.verdict({"a": 0.3, "b": 0.0}, {"a": 0.2, "b": 0})[0]
    assert not check.verdict({"a": 0.1}, {"a": 0.2, "b": 0})[0]
    assert not check.verdict({"a": float("nan")}, {"a": 0.2})[0]
    assert not check.verdict({"a": 0.1, "z": 0.0}, {"a": 0.2})[0]
