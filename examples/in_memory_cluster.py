"""SQL over an in-process multi-worker cluster.

The reference's `examples/in_memory_cluster.rs`: a full coordinator/worker
topology faked inside one process (its InMemoryChannelResolver). Useful as
the first rung of distributed debugging — same planner, codec, and task
lifecycle as a real cluster, no sockets.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pyarrow as pa

from datafusion_distributed_tpu.runtime.coordinator import (
    Coordinator,
    InMemoryCluster,
)
from datafusion_distributed_tpu.sql.context import SessionContext


def main() -> None:
    rng = np.random.default_rng(0)
    n = 10_000
    ctx = SessionContext()
    ctx.register_arrow("orders", pa.table({
        "o_id": np.arange(n),
        "region": rng.integers(0, 5, n),
        "amount": np.round(rng.uniform(1, 500, n), 2),
    }))

    cluster = InMemoryCluster(num_workers=3)
    coordinator = Coordinator(resolver=cluster, channels=cluster)

    df = ctx.sql(
        "select region, count(*) as orders, sum(amount) as revenue "
        "from orders group by region order by revenue desc"
    )
    print("-- staged plan --")
    print(df.explain_distributed(num_tasks=4))
    out = df._strip_quals(
        df.collect_coordinated_table(coordinator=coordinator, num_tasks=4)
    ).to_pandas()
    print("-- result --")
    print(out.to_string(index=False))
    print(f"\nworker task metrics collected: {len(coordinator.metrics)}")


if __name__ == "__main__":
    main()
