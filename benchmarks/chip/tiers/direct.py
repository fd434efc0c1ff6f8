"""Single-node tier: ``ctx.sql(text)`` -> ``to_pandas()``, one jitted
program a query (``plan/physical.py execute_plan``). Takes no arguments."""

from __future__ import annotations

import jax


class Tier:
    def __init__(self, ctx, args: dict, suite):
        self.ctx = ctx

    def run(self, sql: str):
        """The user's one call. -> (pandas frame, overflow retries)."""
        df = self.ctx.sql(sql)
        return df.to_pandas(), df.last_retry_count

    def run_traced(self, sql: str, span):
        """The same work as its public halves, one span around each."""
        with span("bench.parse"):
            df = self.ctx.sql(sql)
        with span("bench.execute"):
            table = jax.block_until_ready(df.collect_table())
        with span("bench.fetch"):
            frame = table.to_pandas()
        return frame, df.last_retry_count

    def close(self) -> None:
        pass
