"""Programs compiled, or loaded from the persistent cache, while the window
was open: ``jax.monitoring`` durations of
``/jax/core/compile/backend_compile_duration``. Anything but 0 is a
finding."""

UNIT = "count"
LAYER = "compile"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    return record["compiles_in_window"]
