"""Rolling upgrade with REAL drain on an elastic cluster.

The reference's `examples/localhost_versioned_run` pair: workers advertise a
version via GetWorkerInfo, and a coordinator built `with_version` refuses to
ship plans to a mixed-version cluster (`worker_service.rs:175-179`). The
membership layer underneath is the reference's dynamic `WorkerResolver`
(SURVEY §1) — here `DynamicCluster`: each worker is upgraded by DRAINING it
(no new tasks; in-flight work finishes; removed only when empty), then
adding its upgraded replacement, which becomes routable immediately. The
cluster serves queries through the whole roll; the version-pinned
coordinator is the safety rail that refuses the mixed-fleet window.
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

import numpy as np
import pyarrow as pa

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.ops.sort import SortKey
from datafusion_distributed_tpu.plan.physical import (
    HashAggregateExec,
    MemoryScanExec,
    SortExec,
)
from datafusion_distributed_tpu.planner.distributed import (
    DistributedConfig,
    distribute_plan,
)
from datafusion_distributed_tpu.runtime.coordinator import (
    Coordinator,
    DynamicCluster,
)
from datafusion_distributed_tpu.runtime.errors import WorkerError
from datafusion_distributed_tpu.runtime.worker import Worker

OLD, NEW = "1.0.3", "1.1.0"


def main() -> None:
    rng = np.random.default_rng(3)
    n = 5_000
    arrow = pa.table({
        "shard": rng.integers(0, 6, n),
        "latency_ms": rng.exponential(20.0, n),
    })
    t = arrow_to_table(arrow)
    plan = SortExec(
        [SortKey("shard")],
        HashAggregateExec(
            "single", ["shard"],
            [AggSpec("avg", "latency_ms", "avg_ms"),
             AggSpec("count_star", None, "n")],
            MemoryScanExec([t], t.schema()),
        ),
    )
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=3))

    cluster = DynamicCluster()
    for i in range(3):
        cluster.add_worker(Worker(f"mem://w{i}-{OLD}", version=OLD))

    serving = Coordinator(resolver=cluster, channels=cluster)
    pinned_new = Coordinator(
        resolver=cluster, channels=cluster, expected_version=NEW,
    )

    print(f"-- fleet on {OLD}, epoch {cluster.membership_epoch} --")
    print(serving.execute(dplan).to_pandas().head(3).to_string(index=False))

    print("\n-- rolling upgrade, one worker at a time (drain -> replace) --")
    for i, url in enumerate(cluster.get_urls()):
        cluster.drain_worker(url)
        assert cluster.wait_drained(url, timeout_s=10.0), (
            f"{url} did not drain"
        )
        print(f"drained+removed {url} "
              f"(in-flight at removal: {cluster.in_flight(url)})")
        cluster.add_worker(Worker(f"mem://w{i}-{NEW}", version=NEW))
        # the cluster keeps serving mid-roll: routing sees live membership
        out = serving.execute(dplan).to_pandas()
        assert len(out) == 6
        if i == 0:
            # mixed-fleet window: the version-pinned coordinator refuses
            print("mixed fleet: ", end="")
            try:
                pinned_new.execute(dplan)
                raise AssertionError("version skew not detected")
            except WorkerError as e:
                print(f"pinned coordinator rejected ({e})")

    snap = cluster.membership_snapshot()
    print(f"\n-- roll complete: epoch {snap['epoch']}, "
          f"active={snap['active']} --")
    out = pinned_new.execute(dplan).to_pandas()
    print(out.to_string(index=False))
    assert len(out) == 6


if __name__ == "__main__":
    main()
