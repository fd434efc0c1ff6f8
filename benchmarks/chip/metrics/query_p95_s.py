"""Nearest-rank 95th percentile of the same walls as ``query_p50_s``. With
fewer than 20 readings it is the slowest query of the window."""

import math

UNIT = "s"
SOURCE = "host_clock"


def read(record: dict):
    walls = sorted(q["end"] - q["start"] for q in record["queries"])
    return walls[math.ceil(0.95 * len(walls)) - 1] if walls else None
