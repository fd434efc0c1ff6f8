"""Meshes-as-workers: each worker owns a device mesh; stage task spans run
as ONE SPMD program per worker, and the host peer-to-peer data plane moves
partitions between the meshes.

This is SURVEY.md §2.10's "same-mesh = collective, off-mesh = host RPC"
topology — the reference's cluster of multi-threaded workers
(`/root/reference/src/worker/worker_service.rs:42-52`) with each worker's
intra-node parallelism provided by a TPU mesh slice instead of a thread
pool. On one host this runs over the 8-device virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/mesh_workers_cluster.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pyarrow as pa

from datafusion_distributed_tpu.runtime.coordinator import Coordinator
from datafusion_distributed_tpu.runtime.mesh_worker import InMemoryMeshCluster
from datafusion_distributed_tpu.sql.context import SessionContext


def main() -> None:
    rng = np.random.default_rng(0)
    n = 50_000
    ctx = SessionContext()
    ctx.register_arrow("orders", pa.table({
        "custkey": rng.integers(0, 1000, n),
        "total": rng.uniform(1, 1000, n).round(2),
    }))
    ctx.register_arrow("customers", pa.table({
        "custkey": np.arange(1000),
        "segment": np.asarray(
            [f"segment-{i % 5}" for i in range(1000)], dtype=object
        ),
    }))
    ctx.config.distributed_options["bytes_per_task"] = 1  # force fan-out

    # two "hosts", each owning half the devices as its private mesh
    cluster = InMemoryMeshCluster(num_workers=2, devices_per_worker=4)
    coord = Coordinator(resolver=cluster, channels=cluster)

    df = ctx.sql(
        "select c.segment, count(*) n, sum(o.total) revenue "
        "from orders o join customers c on o.custkey = c.custkey "
        "group by c.segment order by revenue desc"
    )
    out = df._strip_quals(
        df.collect_coordinated_table(coordinator=coord, num_tasks=8)
    ).to_pandas()
    print(out.to_string(index=False))

    # each worker ran its stage spans as single SPMD programs:
    for url, w in cluster.workers.items():
        print(f"{url}: mesh width {w.mesh_width}, "
              f"{len(w._spans)} span programs executed")
    peer = [m for m in coord.stream_metrics.values()
            if m.get("plane") == "peer"]
    print(f"peer-plane boundaries: {len(peer)} "
          f"(coordinator row bytes: {sum(m['coordinator_bytes'] for m in peer)})")

    single = df.to_pandas()
    assert np.allclose(
        out["revenue"].to_numpy(), single["revenue"].to_numpy(), rtol=1e-4
    ), "distributed result diverged from single-node"
    print("matches single-node execution")


if __name__ == "__main__":
    main()
