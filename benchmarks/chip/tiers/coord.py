"""Coordinator tier as the reference deploys it: one ``ServingSession``
over in-process workers that share the cell's chip, stages split at
exchanges, exchanges through the host. Arguments: ``num_workers``,
``num_tasks``. Overflow retries are the handle's ``retry_count``."""

from __future__ import annotations

import jax

from datafusion_distributed_tpu.io.parquet import table_to_arrow
from datafusion_distributed_tpu.runtime.serving import ServingSession


class Tier:
    def __init__(self, ctx, args: dict, suite):
        self.ctx = ctx
        self.frame = suite.frame
        self.session = ServingSession(ctx, num_workers=args["num_workers"],
                                      num_tasks=args["num_tasks"])

    def run(self, sql: str):
        """The user's one call. -> (pandas frame, overflow retries)."""
        handle = self.session.submit(sql)
        return self.frame(handle.result()), handle.retry_count

    def run_traced(self, sql: str, span):
        """``submit`` parses and plans on the client's thread before it
        queues the query and has no public half for that, so the front end
        is timed by a ``ctx.sql`` of the benchmark's own beforehand (the
        session's plan cache then makes submit's repeat cheap)."""
        with span("bench.parse"):
            self.ctx.sql(sql)
        with span("bench.execute"):
            handle = self.session.submit(sql)
            table = jax.block_until_ready(handle.result_table())
        with span("bench.fetch"):
            frame = self.frame(table_to_arrow(table))
        return frame, handle.retry_count

    def close(self) -> None:
        self.session.close()
