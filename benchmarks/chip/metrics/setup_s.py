"""Process start to the window's start: imports, data (made, or mapped from
the benchmark's cache), registration, the oracle's answer, warm-up with
compile or cache load."""

UNIT = "s"
SOURCE = "host_clock"


def read(record: dict):
    return record["setup"]["setup_s"]
