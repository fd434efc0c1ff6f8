"""A whole distributed query as ONE SPMD program over a device mesh.

This is the TPU-native execution tier with no Rust counterpart: the staged
plan (scan -> partial agg -> all_to_all shuffle -> final agg -> broadcast
join -> coalesce) traces into a single XLA program where the exchanges are
ICI collectives — zero per-stage host round-trips. On a CPU box this runs
over 8 virtual devices; on a TPU slice the identical code uses the chips.
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

import jax

import numpy as np
import pyarrow as pa

from datafusion_distributed_tpu.sql.context import SessionContext


def main() -> None:
    print("devices:", jax.devices())
    rng = np.random.default_rng(2)
    n = 50_000
    ctx = SessionContext()
    ctx.register_arrow("sales", pa.table({
        "store": rng.integers(0, 50, n),
        "item": rng.integers(0, 500, n),
        "qty": rng.integers(1, 20, n).astype(np.int32),
    }))
    ctx.register_arrow("stores", pa.table({
        "store_id": np.arange(50),
        "state": rng.integers(0, 10, 50),
    }))

    df = ctx.sql(
        "select s.state, sum(x.qty) total "
        "from sales x, stores s where x.store = s.store_id "
        "group by s.state order by total desc"
    )
    print("-- staged plan --")
    print(df.explain_distributed(num_tasks=8))
    out = df._strip_quals(df.collect_distributed_table(num_tasks=8))
    print("-- result (computed by one SPMD program) --")
    print(out.to_pandas().to_string(index=False))


if __name__ == "__main__":
    main()
