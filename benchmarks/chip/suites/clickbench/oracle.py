"""Pandas oracle for the ClickBench queries the benchmark runs: the plain
reference, from the columns' string values (never the engine's dictionary
codes), and the comparison that decides ``correct``.

q12 is ``ORDER BY c DESC LIMIT 10`` over counts, so its answer is exact and
still not unique: the phrases tied at the 10th count may be any of them.
The reference answers with every candidate (each phrase whose count is at
least the 10th highest, by count descending); the comparison holds a result
to the configuration's guarantee: as many rows as the LIMIT and the groups
allow, each an expected phrase with exactly its count, every phrase whose
count is above the k-th highest among them, and counts that never rise.
Counts are integers: nothing here has a tolerance.
"""

from __future__ import annotations

import pandas as pd

#: what one comparison measures, each with the most it may read
LIMITS = {"rows_off": 0, "columns_off": 0, "cells_differing": 0,
          "rows_out_of_order": 0}


def top_counts(phrases: pd.Series, limit: int, counts=None) -> pd.DataFrame:
    """The candidates of ``SELECT SearchPhrase, COUNT(*) c ... WHERE
    SearchPhrase <> '' GROUP BY SearchPhrase ORDER BY c DESC LIMIT k``:
    every non-empty phrase whose count is at least the k-th highest,
    ``[SearchPhrase, c]`` by count descending, then phrase. ``counts``
    stands in for pandas' own ``value_counts`` (the control's)."""
    kept = phrases[phrases != ""]
    c = (counts or (lambda s: s.value_counts()))(kept)
    frame = pd.DataFrame({"SearchPhrase": c.index.astype(str),
                          "c": c.to_numpy()})
    frame = frame.sort_values(["c", "SearchPhrase"],
                              ascending=[False, True], ignore_index=True)
    if len(frame) > limit:
        frame = frame[frame.c >= frame.c.iloc[limit - 1]]
    return frame.reset_index(drop=True)


def q12(T, counts=None):
    return top_counts(T["hits"]["SearchPhrase"], 10, counts)


ORACLES = {"q12": q12}


def measure_results(got: pd.DataFrame, expected) -> dict:
    """``expected`` is ``(candidates, limit)``: -> the numbers of
    `LIMITS`. ``rows_off``: how far the row count is from ``min(limit,
    candidates)``; ``columns_off``: from the candidates'; ``cells_differing``:
    the result's cells that are not an expected phrase with its count (a
    phrase named twice differs the second time), and two for each expected
    phrase left out whose count is above the k-th highest;
    ``rows_out_of_order``: adjacent rows whose count rises."""
    exp, limit = expected
    want = dict(zip(exp.SearchPhrase, exp.c))
    rows_off = abs(len(got) - min(limit, len(exp)))
    columns_off = abs(got.shape[1] - exp.shape[1])
    if got.shape[1] < 2 or not len(got):
        return {"rows_off": rows_off, "columns_off": columns_off,
                "cells_differing": 2 * min(limit, len(exp)),
                "rows_out_of_order": 0}
    phrases = [str(p) for p in got.iloc[:, 0]]
    counts = [int(c) for c in got.iloc[:, 1]]
    seen: set = set()
    differing = 0
    for phrase, count in zip(phrases, counts):
        if phrase in seen or phrase not in want:
            differing += 2
        elif want[phrase] != count:
            differing += 1
        seen.add(phrase)
    if len(exp):
        last = exp.c.iloc[min(limit, len(exp)) - 1]
        differing += 2 * sum(1 for p, c in want.items()
                             if c > last and p not in seen)
    order = sum(1 for a, b in zip(counts, counts[1:]) if b > a)
    return {"rows_off": rows_off, "columns_off": columns_off,
            "cells_differing": differing, "rows_out_of_order": order}


def compare_results(got: pd.DataFrame, expected) -> None:
    """`measure_results` held to `LIMITS`. Raises AssertionError on a
    mismatch."""
    numbers = measure_results(got, expected)
    over = {name: value for name, value in numbers.items()
            if value > LIMITS[name]}
    assert not over, f"over the limit: {over} (limits {LIMITS})"
