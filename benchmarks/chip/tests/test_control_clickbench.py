"""The control of ``correct`` for `cb-direct-q12`: the plain reference with
its counts one precision below the configuration's (32-bit device columns;
counts exact), put in the program's place. The tempting step down is to
count in bfloat16, as a one-hot count on the matrix unit would: a bfloat16
accumulator of ones stops growing at 256, so every phrase counted more
often reads low, and the control has to come out as not correct by
``cells_differing`` while its rows and columns stay right. The comparison's
own cases are here too: ties at the 10th count, a phrase left out, counts
out of order, a phrase twice, fewer groups than the LIMIT.

    python benchmarks/chip/tests/test_control_clickbench.py <scale> <seed> [<seed> ...]

prints the control's numbers at any scale. Host arithmetic only: no device.
"""

import functools
import os
import sys

import numpy as np
import pandas as pd
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import run  # noqa: E402

SEEDS = (7, 104729, 2500000001)
#: 20,000 rows of hits: its most popular phrase is counted some 330 times
SCALE = 0.0002

suite = run.load_module("suites", "clickbench", "suite.py")
control = run.load_module("tests", "test_control.py")


@functools.lru_cache(maxsize=None)
def bf16_count(n: int) -> int:
    """``n`` ones summed in `test_control.bf16_sum`'s bfloat16 accumulator."""
    return int(control.bf16_sum(np.ones(n, np.float32))) if n > 256 else n


def bf16_value_counts(phrases: pd.Series) -> pd.Series:
    counts = phrases.value_counts()
    return counts.map(bf16_count)


def control_numbers(scale: float, seed: int) -> dict:
    tables = suite.load(scale, seed, "")
    candidates, limit = suite.expected("q12", tables, bf16_value_counts)
    return suite.measure(candidates.head(limit),
                         suite.expected("q12", tables))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_counting_in_bfloat16_is_not_correct(seed):
    numbers = control_numbers(SCALE, seed)
    assert (numbers["rows_off"], numbers["columns_off"]) == (0, 0)
    assert numbers["cells_differing"] > suite.LIMITS["cells_differing"]


def test_the_reference_agrees_with_itself():
    tables = suite.load(SCALE, SEEDS[0], "")
    candidates, limit = expected = suite.expected("q12", tables)
    assert len(candidates) >= limit
    assert suite.measure(candidates.head(limit), expected) == dict.fromkeys(
        suite.LIMITS, 0)


def _expected(counts: dict, limit: int = 3):
    phrases = pd.Series([p for p, c in counts.items() for _ in range(c)]
                        + [""] * 5)
    return suite.oracle.top_counts(phrases, limit), limit


def _got(rows) -> pd.DataFrame:
    return pd.DataFrame(rows, columns=["SearchPhrase", "c"])


@pytest.mark.parametrize("case,rows,over", [
    # "b", "c" and "d" are tied at the 3rd count: any of them will do
    ("tie_any", [("a", 9), ("e", 5), ("d", 4)], {}),
    ("tie_other", [("a", 9), ("e", 5), ("b", 4)], {}),
    ("count_off", [("a", 9), ("e", 5), ("b", 3)], {"cells_differing": 1}),
    ("left_out", [("a", 9), ("b", 4), ("c", 4)], {"cells_differing": 2}),
    ("not_a_phrase", [("a", 9), ("e", 5), ("z", 4)], {"cells_differing": 2}),
    ("twice", [("a", 9), ("e", 5), ("e", 5)], {"cells_differing": 2}),
    ("out_of_order", [("e", 5), ("a", 9), ("b", 4)],
     {"rows_out_of_order": 1}),
    ("one_short", [("a", 9), ("e", 5)], {"rows_off": 1}),
    ("empty", [], {"rows_off": 3, "cells_differing": 6}),
])
def test_the_comparison_holds_ties_and_finds_each_fault(case, rows, over):
    expected = _expected({"a": 9, "b": 4, "c": 4, "d": 4, "e": 5, "f": 1})
    assert len(expected[0]) == 5  # a, e and the three tied at 4
    numbers = suite.measure(_got(rows), expected)
    assert {k: v for k, v in numbers.items() if v} == over


def test_fewer_groups_than_the_limit():
    expected = _expected({"a": 2, "b": 1}, limit=10)
    assert len(expected[0]) == 2
    assert not any(suite.measure(_got([("a", 2), ("b", 1)]),
                                 expected).values())
    assert suite.measure(_got([("a", 2)]), expected)["rows_off"] == 1


if __name__ == "__main__":
    sys.path.insert(0, run.ROOT)
    for seed in map(int, sys.argv[2:]):
        print("control q12 scale", sys.argv[1], "seed", seed,
              control_numbers(float(sys.argv[1]), seed), flush=True)
