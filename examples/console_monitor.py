"""Live cluster console against a running cluster.

The reference's `console/` TUI: worker discovery + task progress at a poll
interval. This example starts an in-process cluster, runs a query, and
renders a few console frames (point `python -m
datafusion_distributed_tpu.console grpc://host:port` at a real cluster).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pyarrow as pa

from datafusion_distributed_tpu.console import Console
from datafusion_distributed_tpu.runtime.coordinator import (
    Coordinator,
    InMemoryCluster,
)
from datafusion_distributed_tpu.sql.context import SessionContext


def main() -> None:
    cluster = InMemoryCluster(3)
    coordinator = Coordinator(resolver=cluster, channels=cluster)
    rng = np.random.default_rng(4)
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({
        "k": rng.integers(0, 20, 8000), "v": rng.normal(size=8000),
    }))
    df = ctx.sql("select k, avg(v) from t group by k")
    df.collect_coordinated_table(coordinator=coordinator, num_tasks=4)

    console = Console(cluster, cluster, poll_s=0.2)
    console.track(list(coordinator.metrics.keys())[:5])
    console.run(frames=3)


if __name__ == "__main__":
    main()
