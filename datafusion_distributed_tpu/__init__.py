"""datafusion_distributed_tpu — a TPU-native distributed columnar query engine.

A ground-up JAX/XLA re-design of the capability set of
`datafusion-contrib/datafusion-distributed` (reference at /root/reference):
stage-split distributed query execution, with per-stage columnar compute
compiled by XLA onto TPU and shuffle/broadcast exchanges expressed as mesh
collectives instead of gRPC/Arrow-Flight streams.

Layering (mirrors SURVEY.md §1, re-expressed TPU-first):
- ops/       columnar substrate + compute kernels (the DataFusion-L0 analogue)
- plan/      physical plan IR + expression IR
- planner/   distributed planning passes (boundary injection, task counts, …)
- parallel/  mesh + exchange collectives (shuffle/broadcast/coalesce)
- runtime/   coordinator/worker task runtime
- sql/       SQL frontend (parser -> logical plan -> physical plan)
- io/        host-side Parquet/Arrow <-> device Table
- data/      benchmark datasets (TPC-H/TPC-DS/ClickBench generators)
"""

import os as _os

import jax as _jax

# Runtime lock-order / race harness (runtime/lockcheck.py): installed
# FIRST under DFTPU_LOCK_CHECK=1, before any submodule import, so every
# lock the package creates — module-level, class-level and per-instance —
# is wrapped. Observed acquisition order is asserted against the static
# graph (tools/check_concurrency.py); see README "Concurrency model".
if _os.environ.get("DFTPU_LOCK_CHECK", "0") not in ("", "0"):
    from datafusion_distributed_tpu.runtime import lockcheck as _lockcheck

    _lockcheck.install()

# Runtime resource-leak harness (runtime/leakcheck.py): the dynamic half
# of the resource model enforced statically by
# tools/check_resource_lifecycle.py. Installed before submodule imports
# so every tracked acquisition (store entries, spill slots, shm tokens,
# stream pullers, checkpoint slices) is witnessed; see README "Resource
# lifecycle".
if _os.environ.get("DFTPU_LEAK_CHECK", "0") not in ("", "0"):
    from datafusion_distributed_tpu.runtime import leakcheck as _leakcheck

    _leakcheck.install()

# Precision policy: 32-bit TPU-native compute by default; DFTPU_PRECISION=x64
# restores exact f64/i64 (see precision.py for the full rationale).
from datafusion_distributed_tpu import precision  # noqa: F401

from datafusion_distributed_tpu.schema import DataType, Field, Schema  # noqa: E402
from datafusion_distributed_tpu.ops.table import (  # noqa: E402
    Column,
    Dictionary,
    Table,
)

def clear_compile_caches() -> None:
    """Drop every compiled-program cache this package (and jax) holds.

    Long multi-query processes accumulate compiled executables — jax's jit
    caches plus this package's program caches — until the address space
    exhausts (observed: 32-128 MiB allocation failures after ~2 h of SF0.5
    queries). Call between queries in long-lived batch processes; later
    queries recompile, reloading from the persistent compile cache when one
    is configured."""
    from datafusion_distributed_tpu.plan import physical as _phys
    from datafusion_distributed_tpu.runtime import (
        mesh_executor as _me,
        worker as _w,
    )

    _phys._COMPILE_CACHE.clear()
    with _w.Worker._stage_compiles_lock:
        _w.Worker._stage_compiles.clear()
    _me._MESH_COMPILE_CACHE.clear()
    _jax.clear_caches()


__version__ = "0.1.0"

__all__ = [
    "DataType",
    "Field",
    "Schema",
    "Column",
    "Dictionary",
    "Table",
]
