"""Runtime work-unit feeding of a scan.

The reference's `examples/work_unit_feed.rs`: the coordinator discovers
units of work (here: parquet file paths) WHILE the query runs and streams
them to worker tasks in chunks of 256; only the feed's UUID crosses the
wire with the plan. Each unit carries the four lifecycle timestamps
(created/sent/received/processed).
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from datafusion_distributed_tpu.io.parquet import schema_from_arrow
from datafusion_distributed_tpu.plan.physical import execute_plan
from datafusion_distributed_tpu.runtime.work_unit_feed import (
    RemoteWorkUnitFeedRegistry,
    WorkUnitFeedRegistry,
    WorkUnitScanExec,
    stream_feed,
)


def main() -> None:
    # "discovered" inputs: four parquet files written over time
    tmp = tempfile.mkdtemp(prefix="wuf_")
    paths = []
    for i in range(4):
        p = os.path.join(tmp, f"part{i}.parquet")
        pq.write_table(
            pa.table({"x": np.arange(i * 25, (i + 1) * 25)}), p
        )
        paths.append(p)

    registry = WorkUnitFeedRegistry()
    feed_id = registry.register(lambda: iter(paths))
    remote = RemoteWorkUnitFeedRegistry()

    arrow_schema = pq.read_schema(paths[0])
    schema = schema_from_arrow(arrow_schema)
    scan = WorkUnitScanExec(feed_id, schema, capacity=128,
                            remote_registry=remote)

    # coordinator side: route units round-robin to 1 task and close the feed
    sent = stream_feed(
        registry, remote, feed_id,
        task_router=lambda unit, n: 0, task_count=1,
    )
    print(f"streamed {sent} work units")

    out = execute_plan(scan)
    print("rows fed:", int(out.num_rows))
    print("sum(x) =", int(np.asarray(out.to_numpy()["x"]).sum()),
          "(expected", sum(range(100)), ")")


if __name__ == "__main__":
    main()
