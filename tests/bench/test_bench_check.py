"""The compared numbers and their judgement, on readings made by hand."""
from __future__ import annotations

import math

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import check

NAMES = ["a", "b", "c", "d"]


def ref(**kw):
    base = {"names": NAMES, "losses": [10.0, 9.0], "grad0": [1.0, 2.0, 3.0,
                                                           1e-6],
            "update": [1.0, 2.0, 4.0, 1e-6], "momentum": [2.0, 2.0, 2.0, 2.0]}
    base.update(kw)
    return base


def test_numbers_worst_leaf_against_its_norm_or_the_median():
    prog = ref(losses=[10.01, 9.0], update=[1.1, 2.0, 4.0, 5.0],
               momentum=[2.0, 1.0, 2.0, 2.0])
    nums = check.numbers(prog, ref())
    assert nums["loss"] == pytest.approx(1e-3)
    # leaf d is left out: its first gradient is under 1e-3 of the median
    # (2.0 of the kept leaves a, b, c -> median 2.0 floors leaf a's 1.0)
    assert nums["update"] == pytest.approx(0.1 / 2.0)
    assert nums["momentum"] == pytest.approx(0.5)


def test_csc_and_replica_numbers():
    r = ref(hg=[1.0, 1.0, 1.0, 1.0], selected=[True, True, False, False])
    p = ref(hg=[1.0, 1.0, 1.2, 1.0], selected=[True, False, True, False],
            replicas=[0.0, 0.0, 0.4, 0.0])
    nums = check.numbers(p, r)
    assert nums["hg"] == pytest.approx(0.2)
    assert nums["selection"] == pytest.approx(1.0)
    assert nums["replicas"] == pytest.approx(0.1)


def test_mismatched_leaves_refused():
    with pytest.raises(ValueError):
        check.numbers(ref(names=["x", "b", "c", "d"]), ref())


@pytest.mark.parametrize("limits,want", [
    ({"loss": 1e-3, "update": 0.1}, True),
    ({"loss": 1e-5, "update": 0.1}, False),
    ({"loss": 1e-3}, False),                  # a number without a limit
    ({"loss": 1e-3, "update": None}, True),   # named, not compared
])
def test_judge(limits, want):
    ok, rows = check.judge({"loss": 1e-4, "update": 0.05}, limits)
    assert ok is want
    assert [r[0] for r in rows] == ["loss", "update"]


def test_judge_fails_what_is_not_finite():
    assert not check.judge({"loss": math.nan}, {"loss": 1.0})[0]
    assert not check.judge({"loss": math.inf}, {"loss": 1.0})[0]


def test_leaves_neither_side_moved():
    """Under CSC a leaf can go unselected all window: 0 on both sides
    reads 0, a move the reference did not make reads inf."""
    base = ref(update=[0.0, 0.0, 0.0, 0.0])
    assert check.numbers(ref(update=[0.0] * 4), base)["update"] == 0.0
    assert check.numbers(ref(update=[0.0, 1.0, 0.0, 0.0]),
                         base)["update"] == math.inf
