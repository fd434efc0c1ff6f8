"""The control of ``correct``: the plain reference computed one precision
below the configurations' (their files state 32-bit device columns, floats
within 5e-4 relative: bfloat16 is the step a later PR could be tempted by),
put in the program's place. It has to come out as not correct: with every
product rounded to bfloat16 and every sum kept in a bfloat16 accumulator
(fed at most 4096 blocks, each reduced pairwise), a sum stops growing once
it is some 512 addends large, and reads hundreds of tolerances off the
reference, where the program reads well under one (PERF.md section 2 has
both readings at SF1). Rounding alone would not do for a control: the
rounding errors of a pairwise sum cancel, and a one-number answer (q6's)
then lands inside the tolerance on some seeds. Filters and counts stay
exact: the control changes the arithmetic, not the rows.

    python benchmarks/chip/tests/test_control.py <scale> <seed> [<seed> ...]

prints the control's gap for each query the cells send, at any scale (the
SF1 readings of PERF.md come from it). Host arithmetic only: no device.
"""

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


def bf16(values) -> np.ndarray:
    """Rounded to bfloat16 (nearest, ties to even), held as float32."""
    bits = np.ascontiguousarray(values, dtype=np.float32).view(np.uint32)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return rounded.astype(np.uint32).view(np.float32)


def pairwise(v: np.ndarray) -> np.ndarray:
    """The last axis reduced pairwise, every partial sum rounded."""
    while v.shape[-1] > 1:
        if v.shape[-1] % 2:
            v = np.concatenate([v, np.zeros_like(v[..., :1])], axis=-1)
        v = bf16(v[..., 0::2] + v[..., 1::2])
    return v[..., 0]


def bf16_sum(values, steps: int = 4096) -> float:
    """A bfloat16 accumulator: the values in at most ``steps`` blocks, each
    reduced pairwise, added one after another, every sum rounded."""
    v = bf16(values)
    if not len(v):
        return float("nan")
    block = -(-len(v) // steps)
    v = np.concatenate([v, np.zeros(-len(v) % block, np.float32)])
    total = np.float32(0)
    for part in pairwise(v.reshape(-1, block)):
        total = bf16([total + part])[0]
    return float(total)


def control_q6(tables: dict, oracle) -> pd.DataFrame:
    l = oracle.load_pandas({"lineitem": tables["lineitem"]})["lineitem"]
    day = oracle._days
    m = l[(l.l_shipdate >= day("1994-01-01")) & (l.l_shipdate < day("1995-01-01"))
          & (l.l_discount >= 0.05) & (l.l_discount <= 0.07)
          & (l.l_quantity < 24)]
    product = bf16(bf16(m.l_extendedprice) * bf16(m.l_discount))
    return pd.DataFrame({"revenue": [bf16_sum(product)]})


def control_q1(tables: dict, oracle) -> pd.DataFrame:
    l = oracle.load_pandas({"lineitem": tables["lineitem"]})["lineitem"]
    l = l[l.l_shipdate <= oracle._days("1998-09-02")]
    rows = []
    for (flag, status), g in l.groupby(["l_returnflag", "l_linestatus"]):
        qty, price = bf16(g.l_quantity), bf16(g.l_extendedprice)
        disc, tax = bf16(g.l_discount), bf16(g.l_tax)
        disc_price = bf16(price * bf16(1 - disc))
        charge = bf16(disc_price * bf16(1 + tax))
        n = len(g)
        rows.append({
            "l_returnflag": flag, "l_linestatus": status,
            "sum_qty": bf16_sum(qty), "sum_base_price": bf16_sum(price),
            "sum_disc_price": bf16_sum(disc_price),
            "sum_charge": bf16_sum(charge), "avg_qty": bf16_sum(qty) / n,
            "avg_price": bf16_sum(price) / n, "avg_disc": bf16_sum(disc) / n,
            "count_order": n})
    return pd.DataFrame(rows)


CONTROLS = {"q1": control_q1, "q6": control_q6}


def control_numbers(scale: float, seed: int, query: str) -> dict:
    suite = run.load_module("suites", "tpch", "suite.py")
    tables = suite.load(scale, seed, os.path.join(run.CACHE, "data"))
    answer = CONTROLS[query](tables, suite.oracle)
    return suite.measure(answer, suite.expected(query, tables))


def test_every_cells_query_has_a_control():
    """A cell whose query has no control here has no proof that its
    comparison can fail."""
    import json

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    for cell in cells:
        mix = run.read_json("traffic", f"{cell['traffic']}.json")
        assert set(mix["queries"]) <= set(CONTROLS), cell["name"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("query", sorted(CONTROLS))
def test_the_reference_in_bfloat16_is_not_correct(query, seed):
    suite = run.load_module("suites", "tpch", "suite.py")
    numbers = control_numbers(0.01, seed, query)
    # the same rows and groups, the counts exact: only the floats are off,
    # and by three tolerances or more (by hundreds)
    assert (numbers["rows_off"], numbers["columns_off"],
            numbers["cells_differing"]) == (0, 0, 0)
    limit = suite.LIMITS["float_gap_in_tolerances"]
    assert numbers["float_gap_in_tolerances"] > 3 * limit


def test_bf16_rounds_to_eight_bits_of_mantissa():
    assert bf16([1.0, 1.00390625, 1.001]).tolist() == [1.0, 1.0, 1.0]
    assert bf16([1.01171875])[0] == np.float32(1.015625)  # ties to even
    assert bf16_sum([1.0] * 256) == 256.0
    assert bf16_sum([1.0] * 4096) == 256.0  # 256 + 1 rounds back to 256
    assert bf16_sum([1.0] * 8192) == 512.0  # blocks of two
    assert bf16_sum(np.full(3, 0.1)) == pytest.approx(0.3, rel=1e-2)
    assert np.isnan(bf16_sum([]))


if __name__ == "__main__":
    for seed in map(int, sys.argv[2:]):
        for query in sorted(CONTROLS):
            print("control", query, "scale", sys.argv[1], "seed", seed,
                  control_numbers(float(sys.argv[1]), seed, query), flush=True)
