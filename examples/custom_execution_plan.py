"""A user-defined ExecutionPlan operator running distributed.

The reference's `examples/custom_execution_plan.rs`: implement a custom
physical operator, register a codec for it, and watch it survive the full
distributed lifecycle — plan staging, serialization, shipment to workers,
decode, and execution inside each task's traced XLA program.

The operator here is `WinsorizeExec`: clamps a numeric column to the
[lo, hi] quantile band estimated from each task's local shard. It is a
single-child, capacity-preserving node — the simplest shape of custom
operator — and composes with the engine's own exchanges (the plan below
shuffles by key after winsorizing, then aggregates).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.ops.table import Column, Table
from datafusion_distributed_tpu.plan.physical import (
    ExecContext,
    ExecutionPlan,
    HashAggregateExec,
    MemoryScanExec,
    SortExec,
)
from datafusion_distributed_tpu.ops.sort import SortKey
from datafusion_distributed_tpu.planner.distributed import (
    DistributedConfig,
    distribute_plan,
)
from datafusion_distributed_tpu.runtime.codec import register_codec
from datafusion_distributed_tpu.runtime.coordinator import (
    Coordinator,
    InMemoryCluster,
)


class WinsorizeExec(ExecutionPlan):
    """Clamp `column` to its local [q, 1-q] quantile band.

    Everything a custom node must provide: the tree contract
    (children / with_new_children), schema + output_capacity (static shapes
    are what make the node XLA-traceable), and `_execute`, which runs at
    TRACE time — jnp ops only, no data-dependent Python control flow."""

    codec_kind = "winsorize"  # ties the node to its registered codec

    def __init__(self, child: ExecutionPlan, column: str, q: float):
        super().__init__()
        self.child = child
        self.column = column
        self.q = q

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return WinsorizeExec(children[0], self.column, self.q)

    def schema(self):
        return self.child.schema()

    def output_capacity(self):
        return self.child.output_capacity()

    def label(self):
        return f"Winsorize({self.column}, q={self.q})"

    def _execute(self, ctx: ExecContext) -> Table:
        t = self.child.execute(ctx)
        i = t.names.index(self.column)
        col = t.columns[i]
        live = t.row_mask()
        # quantiles over live rows only (padding is masked to NaN)
        vals = jnp.where(live, col.data, jnp.nan)
        lo = jnp.nanquantile(vals, self.q)
        hi = jnp.nanquantile(vals, 1.0 - self.q)
        clamped = jnp.clip(col.data, lo, hi)
        cols = list(t.columns)
        cols[i] = Column(clamped, col.validity, col.dtype, col.dictionary)
        # a custom metric, visible in explain_analyze / coordinator metrics
        ctx.record_metric(self, "clamped_rows",
                          jnp.sum((col.data != clamped) & live))
        return Table(t.names, tuple(cols), t.num_rows)


# The codec pair: encode -> JSON-able dict, decode -> node. Registered once
# per process; workers decoding a shipped plan look the kind up in the same
# registry (`runtime/codec.py` register_codec, the user-codec registry
# analogue of `src/protobuf/user_codec.rs`).
register_codec(
    "winsorize",
    lambda p, store: {
        "column": p.column,
        "q": p.q,
        "c": __import__(
            "datafusion_distributed_tpu.runtime.codec", fromlist=["encode_plan"]
        ).encode_plan(p.child, store),
    },
    lambda o, store: WinsorizeExec(
        __import__(
            "datafusion_distributed_tpu.runtime.codec", fromlist=["decode_plan"]
        ).decode_plan(o["c"], store),
        o["column"],
        o["q"],
    ),
)


def main() -> None:
    rng = np.random.default_rng(7)
    n = 20_000
    # heavy-tailed values: winsorizing changes the group sums visibly
    arrow = pa.table({
        "k": rng.integers(0, 8, n),
        "v": rng.standard_t(df=2, size=n) * 100,
    })
    t = arrow_to_table(arrow)

    scan = MemoryScanExec([t], t.schema())
    custom = WinsorizeExec(scan, "v", q=0.01)
    agg = HashAggregateExec(
        "single", ["k"],
        [AggSpec("sum", "v", "winsorized_sum"),
         AggSpec("count_star", None, "n")],
        custom,
    )
    plan = SortExec([SortKey("k")], agg)

    dplan = distribute_plan(plan, DistributedConfig(num_tasks=4))
    print("-- staged plan (custom node inside the task pipeline) --")
    print(dplan.display_tree())

    cluster = InMemoryCluster(num_workers=3)
    coord = Coordinator(resolver=cluster, channels=cluster)
    out = coord.execute(dplan).to_pandas()
    print("\n-- result (winsorized group sums) --")
    print(out.to_string(index=False))

    clamped = sum(
        m.get("clamped_rows", 0)
        for task in coord.metrics.values()
        for m in task.get("nodes", {}).values()
        if isinstance(m, dict)
    )
    print(f"\nrows clamped across all tasks: {clamped}")
    assert len(out) == 8


if __name__ == "__main__":
    main()
