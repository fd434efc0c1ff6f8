"""Queries that completed and agreed with the reference, over the time from
the window's start to the last completion."""

UNIT = "queries/s"
SOURCE = "host_clock"


def read(record: dict):
    done = sum(1 for q in record["queries"] if q["ok"])
    return done / record["window_s"] if done else None
