"""Host clock around the ``register_arrow`` loop over all tables: host
dictionary encoding of strings, then H2D."""

UNIT = "s"
LAYER = "ingestion"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(record: dict):
    return record["setup"]["register_s"]
