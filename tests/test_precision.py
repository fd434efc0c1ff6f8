"""Precision-mode policy tests (see datafusion_distributed_tpu/precision.py).

The flagship claim is that in tpu mode NO 64-bit op can reach the device:
TPU hardware emulates f64/i64 an order of magnitude slower, so a single
stray wide op in a hot kernel silently wrecks performance. The audit
traces real kernels to jaxprs and scans every equation's avals.
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from datafusion_distributed_tpu import precision
from datafusion_distributed_tpu.ops.aggregate import AggSpec, hash_aggregate
from datafusion_distributed_tpu.ops.table import Table
from datafusion_distributed_tpu.schema import DataType, Field, Schema


def _64bit_dtypes_in_jaxpr(jaxpr) -> set:
    found = set()

    def scan(jx):
        for eqn in jx.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None and np.dtype(dt).itemsize == 8:
                    found.add((eqn.primitive.name, str(dt)))
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    scan(sub.jaxpr)
                elif isinstance(sub, (list, tuple)):
                    for s in sub:
                        if hasattr(s, "jaxpr"):
                            scan(s.jaxpr)
    scan(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return found


@pytest.mark.skipif(precision.MODE != "tpu", reason="tpu mode only")
def test_no_64bit_ops_in_aggregate_kernel():
    assert not jax.config.jax_enable_x64
    schema = Schema([
        Field("k", DataType.INT64, nullable=False),
        Field("v", DataType.FLOAT64, nullable=False),
    ])
    t = Table.from_numpy(
        {"k": np.arange(64) % 7, "v": np.linspace(0, 1, 64)}, schema
    )
    aggs = [
        AggSpec("sum", "v", "sv"),
        AggSpec("avg", "v", "av"),
        AggSpec("count_star", None, "n"),
        AggSpec("min", "v", "mn"),
    ]
    jx = jax.make_jaxpr(
        lambda tt: hash_aggregate(tt, ["k"], aggs, num_slots=16)
    )(t)
    wide = _64bit_dtypes_in_jaxpr(jx)
    assert not wide, f"64-bit ops leaked into the tpu-mode kernel: {wide}"


@pytest.mark.skipif(precision.MODE != "tpu", reason="tpu mode only")
def test_storage_dtypes_narrowed():
    assert DataType.INT64.np_dtype == np.dtype(np.int32)
    assert DataType.FLOAT64.np_dtype == np.dtype(np.float32)
    assert DataType.INT64.logical_np_dtype == np.dtype(np.int64)
    assert DataType.INT32.np_dtype == np.dtype(np.int32)


@pytest.mark.skipif(precision.MODE != "tpu", reason="tpu mode only")
def test_int_narrowing_overflow_is_loud():
    schema = Schema([Field("k", DataType.INT64, nullable=False)])
    with pytest.raises(OverflowError, match="DFTPU_PRECISION=x64"):
        Table.from_numpy({"k": np.asarray([2**40], dtype=np.int64)}, schema)


@pytest.mark.skipif(precision.MODE != "tpu", reason="tpu mode only")
def test_int32_sum_range_exceeded_is_loud_and_not_retried():
    """Integer SUM past 2^31 in tpu mode raises `PrecisionRangeError`,
    which is no capacity overflow: nothing retries it."""
    from datafusion_distributed_tpu.plan.physical import (
        HashAggregateExec, MemoryScanExec, execute_plan,
    )
    from datafusion_distributed_tpu.ops.aggregate import AggSpec

    schema = Schema([
        Field("k", DataType.INT32, nullable=False),
        Field("v", DataType.INT32, nullable=False),
    ])
    t = Table.from_numpy(
        {
            "k": np.zeros(8, dtype=np.int32),
            "v": np.full(8, 2**29, dtype=np.int32),
        },
        schema,
    )
    plan = HashAggregateExec(
        "single", ["k"], [AggSpec("sum", "v", "sv")],
        MemoryScanExec([t], schema), num_slots=8,
    )
    from datafusion_distributed_tpu.runtime.errors import (
        PrecisionRangeError,
        is_capacity_overflow,
    )

    with pytest.raises(PrecisionRangeError) as e:
        execute_plan(plan, use_cache=False)
    assert not is_capacity_overflow(e.value)
    assert "DFTPU_PRECISION=x64" in str(e.value)


@pytest.mark.skipif(precision.MODE != "tpu", reason="tpu mode only")
def test_parquet_ingest_narrowing_is_loud(tmp_path):
    """int64 values past int32 range must fail loudly at ingest, not wrap
    (the Column.from_numpy guard must see the wide array)."""
    pa = pytest.importorskip("pyarrow")
    import pyarrow.parquet as pq

    from datafusion_distributed_tpu.io.parquet import read_parquet

    path = tmp_path / "wide.parquet"
    pq.write_table(pa.table({"k": pa.array([2**40], type=pa.int64())}), path)
    with pytest.raises(OverflowError, match="DFTPU_PRECISION=x64"):
        read_parquet(str(path))


def test_x64_mode_exact_in_subprocess():
    """DFTPU_PRECISION=x64 restores full-width storage (runs in a clean
    interpreter because the mode is import-time-frozen)."""
    code = (
        "import os; os.environ['DFTPU_PRECISION']='x64';"
        "os.environ['JAX_PLATFORMS']='cpu';"
        "import jax; jax.config.update('jax_platforms','cpu');"
        "import numpy as np;"
        "from datafusion_distributed_tpu.schema import DataType;"
        "assert DataType.INT64.np_dtype == np.dtype(np.int64);"
        "assert DataType.FLOAT64.np_dtype == np.dtype(np.float64);"
        "print('ok')"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "ok" in out.stdout
