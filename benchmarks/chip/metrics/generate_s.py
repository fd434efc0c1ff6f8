"""Host clock around the suite's ``load``: ``gen_tpch`` in a checkout's
first run with a seed, a map of the cached Arrow files afterwards."""

UNIT = "s"
LAYER = "data generation"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(record: dict):
    return record["setup"]["generate_s"]
