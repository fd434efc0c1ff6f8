"""Real gRPC workers on localhost ports.

The reference's `examples/localhost_run/worker.rs`: every worker is a real
network service; plans ship as compressed binary frames and results stream
back chunked (zstd Arrow IPC — see runtime/transport.py). The same code
deploys multi-host by starting `serve_worker` on each machine and pointing
the resolver at their URLs.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pyarrow as pa

from datafusion_distributed_tpu.runtime.coordinator import Coordinator
from datafusion_distributed_tpu.runtime.grpc_worker import (
    start_localhost_cluster,
)
from datafusion_distributed_tpu.sql.context import SessionContext


def main() -> None:
    cluster = start_localhost_cluster(num_workers=2)
    print("workers:", cluster.get_urls())
    try:
        rng = np.random.default_rng(1)
        n = 20_000
        ctx = SessionContext()
        ctx.register_arrow("events", pa.table({
            "kind": rng.integers(0, 8, n),
            "ms": rng.exponential(20.0, n),
        }))
        coordinator = Coordinator(resolver=cluster, channels=cluster)
        df = ctx.sql(
            "select kind, count(*) n, avg(ms) avg_ms, max(ms) worst "
            "from events group by kind order by kind"
        )
        out = df._strip_quals(
            df.collect_coordinated_table(coordinator=coordinator,
                                         num_tasks=4)
        ).to_pandas()
        print(out.to_string(index=False))
    finally:
        cluster.shutdown()


if __name__ == "__main__":
    main()
