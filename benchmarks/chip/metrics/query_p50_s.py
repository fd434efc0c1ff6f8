"""Median wall of one query of the window, call to pandas frame on the
host, over every query started (``statistics.median``)."""

import statistics

UNIT = "s"
SOURCE = "host_clock"


def read(record: dict):
    walls = [q["end"] - q["start"] for q in record["queries"]]
    return statistics.median(walls) if walls else None
