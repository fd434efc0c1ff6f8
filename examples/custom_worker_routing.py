"""User-controlled task->worker routing.

The reference's `examples/custom_worker_url_routing.rs`: by default tasks
round-robin over workers; a `route_tasks` hook pins them (data locality,
heterogeneous hardware, tenancy). Here even stages go to worker 0, odd to
worker 1, and the routing log proves it.
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

ROUTES = []


def route_by_stage(query_id, stage_id, task_number, urls):
    url = urls[abs(stage_id) % len(urls)]
    ROUTES.append((stage_id, task_number, url))
    return url


def main() -> None:
    rng = np.random.default_rng(3)
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({
        "k": rng.integers(0, 30, 5000), "v": rng.normal(size=5000),
    }))
    cluster = InMemoryCluster(2)
    coordinator = Coordinator(
        resolver=cluster, channels=cluster, route_tasks=route_by_stage
    )
    df = ctx.sql("select k, sum(v) sv from t group by k order by sv desc")
    out = df._strip_quals(
        df.collect_coordinated_table(coordinator=coordinator, num_tasks=4)
    ).to_pandas()
    print(out.head(5).to_string(index=False))
    print("\nrouting decisions (stage, task) -> worker:")
    for stage, task, url in ROUTES:
        print(f"  ({stage}, {task}) -> {url}")


if __name__ == "__main__":
    main()
