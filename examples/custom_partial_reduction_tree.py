"""Hand-built distributed plan: a progressive partial-reduction tree.

The reference's `examples/custom_distributed_partial_reduction_tree.rs`:
exchange nodes are public, constructible operators — if a plan ALREADY
contains boundaries when it reaches the distributed planner, the planner
does not re-distribute it; it only finalizes what you placed
(`distributed_query_planner.rs:78-99`). Here that is used to build a
GROUP BY reduction tree that shrinks data at every level instead of one
wide gather:

    Final               (1 task)    <- finishes the aggregation
      CoalesceExchange  M -> 1
    PartialReduce       (M tasks)   <- merges partial STATES (fewer states
      CoalesceExchange  N -> M         cross each hop; avg merges its
    Partial             (N tasks)      (sum, count) pair correctly)
      MemoryScan        N slices

`HashAggregateExec(mode="partial_reduce")` is the key node: unlike a plain
coalesce (which only concatenates), it re-groups and merges accumulator
columns while KEEPING them in state form, so a later final stage can finish
the job (`ops/aggregate.py` partial_reduce mode; the reference's
AggregateMode::PartialReduce).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# JAX_PLATFORMS decides: unset, this runs on the CPU's virtual mesh;
# JAX_PLATFORMS=tpu uses the real chips (the device-count flag is CPU-only)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pyarrow as pa

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.ops.sort import SortKey
from datafusion_distributed_tpu.plan.exchanges import CoalesceExchangeExec
from datafusion_distributed_tpu.plan.physical import (
    HashAggregateExec,
    MemoryScanExec,
    SortExec,
)
from datafusion_distributed_tpu.planner.distributed import (
    DistributedConfig,
    distribute_plan,
)
from datafusion_distributed_tpu.parallel.exchange import partition_table
from datafusion_distributed_tpu.runtime.mesh_executor import (
    execute_on_mesh,
    make_mesh,
)

N_TASKS = 8  # leaf fan-in
M_GROUPS = 2  # intermediate reduction width


def main() -> None:
    rng = np.random.default_rng(5)
    n = 80_000
    # "weather": station-keyed readings, like the reference example's table
    arrow = pa.table({
        "station": rng.integers(0, 12, n),
        "temp_c": np.round(rng.normal(15, 9, n), 2),
    })
    t = arrow_to_table(arrow)

    scan = MemoryScanExec(partition_table(t, N_TASKS), t.schema())
    aggs = [
        AggSpec("avg", "temp_c", "avg_temp"),
        AggSpec("max", "temp_c", "max_temp"),
        AggSpec("count_star", None, "readings"),
    ]
    partial = HashAggregateExec("partial", ["station"], aggs, scan)
    narrow = CoalesceExchangeExec(partial, N_TASKS, num_consumers=M_GROUPS)
    reduce_ = HashAggregateExec("partial_reduce", ["station"], aggs, narrow)
    gather = CoalesceExchangeExec(reduce_, N_TASKS)
    final = HashAggregateExec("final", ["station"], aggs, gather)
    plan = SortExec([SortKey("station")], final)

    # the planner sees the hand-placed boundaries and only finalizes them
    staged = distribute_plan(plan, DistributedConfig(num_tasks=N_TASKS))
    print("-- hand-built reduction tree (as finalized by the planner) --")
    print(staged.display_tree())

    mesh = make_mesh(N_TASKS)
    out = execute_on_mesh(staged, mesh).to_pandas()
    print("\n-- result (one SPMD program over the mesh) --")
    print(out.to_string(index=False))

    # oracle check: the tree must agree with plain pandas
    exp = (
        arrow.to_pandas().groupby("station")
        .agg(avg_temp=("temp_c", "mean"), max_temp=("temp_c", "max"),
             readings=("temp_c", "size"))
        .reset_index().sort_values("station").reset_index(drop=True)
    )
    np.testing.assert_allclose(out["avg_temp"], exp["avg_temp"], rtol=1e-5)
    np.testing.assert_allclose(out["max_temp"], exp["max_temp"], rtol=1e-6)
    np.testing.assert_array_equal(out["readings"], exp["readings"])
    print("\nmatches the pandas oracle ✓")


if __name__ == "__main__":
    main()
