"""The forms a column without NULLs can take, for the tests that hold the
engine to "a validity of None reads as all true" (PR 37)."""

import jax.numpy as jnp
import numpy as np

from datafusion_distributed_tpu.ops.table import Table


def with_all_true_masks(table: Table) -> Table:
    """``table`` with a validity array on every column that has none: true
    over the rows, false over the padding, which is what arrow ingestion
    gave EVERY column until PR 37 (`np.ones(rows)` padded with zeros)."""
    mask = jnp.asarray(np.arange(table.capacity) < int(table.num_rows))
    return Table(
        table.names,
        tuple(c if c.validity is not None else c.with_validity(mask)
              for c in table.columns),
        table.num_rows,
    )


def force_all_true_masks(ctx) -> None:
    """Re-register every table of ``ctx`` in the masked form."""
    for name, table in list(ctx.catalog.tables.items()):
        ctx.register_table(name, with_all_true_masks(table))
