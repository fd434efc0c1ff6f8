"""Mesh tier: ``collect_distributed(mesh=make_mesh(n))``, one ``shard_map``
program over ``num_tasks`` chips, exchanges as collectives over ICI.
``make_mesh`` refuses more tasks than there are devices."""

from __future__ import annotations

import jax

from datafusion_distributed_tpu.io.parquet import table_to_arrow
from datafusion_distributed_tpu.runtime.mesh_executor import make_mesh


class Tier:
    def __init__(self, ctx, args: dict, suite):
        self.ctx = ctx
        self.frame = suite.frame
        self.mesh = make_mesh(args["num_tasks"])

    def run(self, sql: str):
        """The user's one call. -> (pandas frame, overflow retries)."""
        df = self.ctx.sql(sql)
        arrow = df.collect_distributed(mesh=self.mesh)
        return self.frame(arrow), df.last_retry_count

    def run_traced(self, sql: str, span):
        """The same work as its public halves, one span around each."""
        with span("bench.parse"):
            df = self.ctx.sql(sql)
        with span("bench.execute"):
            table = jax.block_until_ready(
                df.collect_distributed_table(mesh=self.mesh))
        with span("bench.fetch"):
            frame = self.frame(table_to_arrow(table))
        return frame, df.last_retry_count

    def close(self) -> None:
        pass
