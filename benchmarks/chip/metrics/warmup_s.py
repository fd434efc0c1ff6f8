"""Host clock around the warm-up: each query of the mix twice, the first
call compiling or loading from the persistent compile cache."""

UNIT = "s"
LAYER = "compile"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(record: dict):
    return record["setup"]["warmup_s"]
