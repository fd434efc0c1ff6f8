"""Coordinator/worker runtime tests (in-memory cluster).

The reference's integration tier (SURVEY.md §4): plan shipping, task
registry TTL, structured error propagation, distributed-vs-single parity
through the worker path.
"""

import time

import numpy as np

from datafusion_distributed_tpu import precision as _precision

# f32 compute in tpu precision mode: summation-order differences are ~eps
FLOAT_RTOL = _precision.test_rtol()

import pyarrow as pa
import pytest

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
from datafusion_distributed_tpu.runtime.codec import (
    TableStore,
    decode_plan,
    decode_table,
    encode_plan,
    encode_table,
)
from datafusion_distributed_tpu.runtime.coordinator import (
    Coordinator,
    InMemoryCluster,
)
from datafusion_distributed_tpu.runtime.errors import WorkerError
from datafusion_distributed_tpu.runtime.worker import (
    TaskKey,
    TaskRegistry,
    TaskData,
    Worker,
)

NT = 4


def _cluster(n=3):
    c = InMemoryCluster(n)
    return Coordinator(resolver=c, channels=c)


def sample_plan(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    arrow = pa.table({"k": rng.integers(0, 25, n), "v": rng.normal(size=n)})
    t = arrow_to_table(arrow)
    scan = MemoryScanExec([t], t.schema())
    agg = HashAggregateExec(
        "single", ["k"],
        [AggSpec("sum", "v", "sv"), AggSpec("count_star", None, "n")],
        scan,
    )
    return SortExec([SortKey("k")], agg), arrow


def test_codec_roundtrip():
    plan, _ = sample_plan(100)
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=NT))
    store = TableStore()
    obj = encode_plan(dplan, store)
    import json

    json.dumps({k: v for k, v in obj.items() if k != "tables"})  # JSON-able
    back = decode_plan(obj, store)
    assert back.display_tree().replace(" ", "") != ""
    # same structure
    assert type(back).__name__ == type(dplan).__name__
    assert len(back.collect(lambda n: True)) == len(dplan.collect(lambda n: True))


def test_table_ipc_roundtrip():
    arrow = pa.table({"a": [1, 2, None], "s": ["x", None, "z"]})
    t = arrow_to_table(arrow)
    data = encode_table(t)
    # encode_table returns a buffer-protocol view over the Arrow buffer
    # (no getvalue() duplication); the wire framing consumes it as-is
    assert isinstance(data, (bytes, memoryview)) and len(data) > 0
    back = decode_table(data)
    assert back.to_pandas()["a"].fillna(-1).tolist() == [1, 2, -1]
    assert back.to_pandas()["s"].fillna("@").tolist() == ["x", "@", "z"]


def test_wire_dictionary_gc():
    """Shipped slices re-encode string dictionaries to only the values the
    live rows reference (the reference's pre-Flight dictionary GC,
    `impl_execute_task.rs:244-274`): a selective filter shrinks the wire
    bytes by orders of magnitude, and the receiver adopts the compacted
    dictionary directly."""
    import jax.numpy as jnp

    vals = [f"value_{i:04d}" for i in range(1000)]
    arrow = pa.table({
        "s": np.asarray(vals * 20, dtype=object),
        "x": np.arange(20000),
    })
    t = arrow_to_table(arrow)
    full_bytes = len(encode_table(t))
    keep = (np.arange(t.capacity) % 1000 < 10) & (
        np.arange(t.capacity) < 20000
    )
    filtered = t.compact(jnp.asarray(keep))
    wire = encode_table(filtered)
    assert len(wire) < full_bytes / 10, (len(wire), full_bytes)
    back = decode_table(wire)
    col = back.column("s")
    # GC: only the 10 referenced values shipped; sorted order preserved
    assert len(col.dictionary.values) == 10
    assert list(col.dictionary.values) == sorted(col.dictionary.values)
    pdf = back.to_pandas().sort_values("x").reset_index(drop=True)
    exp = (
        arrow.to_pandas()[lambda d: d.x % 1000 < 10]
        .sort_values("x").reset_index(drop=True)
    )
    assert (pdf["s"] == exp["s"]).all()
    assert (pdf["x"] == exp["x"]).all()


def test_coordinator_executes_distributed_plan():
    plan, arrow = sample_plan()
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=NT))
    coord = _cluster(3)
    out = coord.execute(dplan).to_pandas()
    exp = (
        arrow.to_pandas().groupby("k")
        .agg(sv=("v", "sum"), n=("v", "size")).reset_index()
        .sort_values("k").reset_index(drop=True)
    )
    np.testing.assert_array_equal(out["k"], exp["k"])
    np.testing.assert_allclose(out["sv"], exp["sv"], rtol=FLOAT_RTOL)
    np.testing.assert_array_equal(out["n"], exp["n"])
    # metrics were collected per task
    assert len(coord.metrics) > 0
    assert all("elapsed_s" in m for m in coord.metrics.values())


def test_task_registry_ttl():
    reg = TaskRegistry(ttl_seconds=0.05)
    key = TaskKey("q", 0, 0)
    reg.put(TaskData(key=key, plan=None, task_count=1))
    assert reg.get(key) is not None
    time.sleep(0.08)
    reg.put(TaskData(key=TaskKey("q2", 0, 0), plan=None, task_count=1))  # evicts
    assert reg.get(key) is None


def test_worker_error_propagation():
    w = Worker("mem://w0")
    key = TaskKey("q", 0, 0)
    with pytest.raises(WorkerError) as ei:
        w.execute_task(key)
    assert "no plan" in str(ei.value)
    assert ei.value.worker_url == "mem://w0"
    # structured round trip
    d = ei.value.to_dict()
    back = WorkerError.from_dict(d)
    assert back.worker_url == "mem://w0"
    assert back.task == key


def test_worker_on_plan_hook():
    seen = []

    def hook(plan, key):
        seen.append(key)
        return plan

    cluster = InMemoryCluster(2)
    for w in cluster.workers.values():
        w.on_plan = hook
    coord = Coordinator(resolver=cluster, channels=cluster)
    plan, arrow = sample_plan(500)
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=2))
    coord.execute(dplan)
    assert len(seen) > 0


def test_sql_through_coordinator():
    from datafusion_distributed_tpu.sql.context import DataFrame, SessionContext

    rng = np.random.default_rng(5)
    ctx = SessionContext()
    ctx.register_arrow("f", pa.table({
        "k": rng.integers(0, 10, 1000), "v": rng.normal(size=1000)}))
    ctx.register_arrow("d", pa.table({"k": np.arange(10),
                                      "w": rng.normal(size=10)}))
    sql = ("select f.k, sum(f.v + d.w) s from f, d where f.k = d.k "
           "group by f.k order by f.k")
    df = ctx.sql(sql)
    single = df.to_pandas()
    dplan = df.distributed_plan(NT)
    out = DataFrame._strip_quals(_cluster(2).execute(dplan)).to_pandas()
    np.testing.assert_array_equal(out["k"], single["k"])
    np.testing.assert_allclose(out["s"], single["s"], rtol=FLOAT_RTOL)


def test_metrics_and_explain_analyze():
    from datafusion_distributed_tpu.plan.physical import execute_plan
    from datafusion_distributed_tpu.runtime.metrics import (
        MetricsStore,
        explain_analyze,
    )

    plan, arrow = sample_plan(300, seed=9)
    store = MetricsStore()
    execute_plan(plan, metrics_store=store, task_label="task0")
    text = explain_analyze(plan, store)
    assert "output_rows=" in text
    assert "Sort" in text and "HashAggregate" in text
    # aggregated rows of the scan must equal the input row count
    agg = store.aggregated()
    scan_id = plan.collect(lambda n: not n.children())[0].node_id
    assert agg[scan_id]["output_rows"] == 300
    # PerTask format labels metrics with the task
    per = explain_analyze(plan, store, per_task=True)
    assert "output_rows_task0=" in per


def test_mesh_metrics_per_task():
    from datafusion_distributed_tpu.runtime.mesh_executor import (
        execute_on_mesh,
        make_mesh,
    )
    from datafusion_distributed_tpu.runtime.metrics import MetricsStore

    plan, arrow = sample_plan(800, seed=11)
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=4))
    store = MetricsStore()
    mesh = make_mesh(4)
    execute_on_mesh(dplan, mesh, metrics_store=store)
    assert len(store.per_task) == 4
    # scan rows across tasks sum to the input size
    agg = store.aggregated()
    scans = dplan.collect(lambda n: not n.children())
    total = sum(agg.get(s.node_id, {}).get("output_rows", 0) for s in scans)
    assert total == 800


def test_make_mesh_refuses_more_tasks_than_devices():
    import jax

    from datafusion_distributed_tpu.runtime.mesh_executor import make_mesh

    assert len(jax.devices()) == 8  # tests/conftest.py
    assert make_mesh(8).shape["tasks"] == 8
    # a silent devices[:n] slice would hide the device count (a 4-task
    # query on a one-chip host running on one device without a word)
    with pytest.raises(ValueError, match="16 tasks.*8 device"):
        make_mesh(16)


def test_observability_service():
    from datafusion_distributed_tpu.runtime.observability import (
        ObservabilityService,
        sample_system_metrics,
    )

    cluster = InMemoryCluster(2)
    coord = Coordinator(resolver=cluster, channels=cluster)
    plan, _ = sample_plan(300)
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=2))
    coord.execute(dplan)
    obs = ObservabilityService(cluster, cluster)
    assert obs.ping()["ok"]
    workers = obs.get_cluster_workers()
    assert len(workers) == 2 and all("version" in w for w in workers)
    m = sample_system_metrics()
    assert m.rss_bytes > 0


def test_set_option_flows_to_distributed_config():
    from datafusion_distributed_tpu.sql.context import SessionContext

    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0]}))
    assert ctx.sql("set distributed.broadcast_joins = false") is None
    assert ctx.config.distributed_options["broadcast_joins"] is False
    df = ctx.sql("select k from t where v > 1 order by k")
    dplan = df.distributed_plan(2)
    assert dplan is not None
    ctx.sql("set planner.join_expansion_factor = 2.0")
    assert ctx.config.planner.join_expansion_factor == 2.0


def test_grpc_localhost_cluster():
    """Distributed execution over real gRPC sockets (localhost), matching
    the in-memory path (the reference's start_localhost_context tier)."""
    from datafusion_distributed_tpu.runtime.grpc_worker import (
        start_localhost_cluster,
    )

    plan, arrow = sample_plan(1200, seed=21)
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=3))
    cluster = start_localhost_cluster(2)
    try:
        coord = Coordinator(resolver=cluster, channels=cluster)
        out = coord.execute(dplan).to_pandas()
        exp = (
            arrow.to_pandas().groupby("k")
            .agg(sv=("v", "sum"), n=("v", "size")).reset_index()
            .sort_values("k").reset_index(drop=True)
        )
        np.testing.assert_array_equal(out["k"], exp["k"])
        np.testing.assert_allclose(out["sv"], exp["sv"], rtol=FLOAT_RTOL)
        np.testing.assert_array_equal(out["n"], exp["n"])
        # observability over gRPC too
        infos = [cluster.get_worker(u).get_info() for u in cluster.get_urls()]
        assert all("version" in i for i in infos)
    finally:
        cluster.shutdown()


def test_grpc_error_propagation():
    from datafusion_distributed_tpu.runtime.grpc_worker import (
        start_localhost_cluster,
    )
    from datafusion_distributed_tpu.runtime.worker import TaskKey

    cluster = start_localhost_cluster(1)
    try:
        client = cluster.get_worker(cluster.get_urls()[0])
        with pytest.raises(WorkerError) as ei:
            client.execute_task(TaskKey("nope", 0, 0))
        assert "no plan" in str(ei.value)
    finally:
        cluster.shutdown()


def test_grpc_metrics_collected():
    from datafusion_distributed_tpu.runtime.grpc_worker import (
        start_localhost_cluster,
    )

    plan, _ = sample_plan(400, seed=31)
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=2))
    cluster = start_localhost_cluster(1)
    try:
        coord = Coordinator(resolver=cluster, channels=cluster)
        coord.execute(dplan)
        assert len(coord.metrics) > 0
        assert any(m and "elapsed_s" in m for m in coord.metrics.values())
    finally:
        cluster.shutdown()


def test_partition_range_accounting():
    """Partition-range data plane (`worker_connection_pool.rs:243-308`):
    two disjoint range requests serve the task's hash-partitioned output
    once, chunks arrive tagged by partition, and the registry entry
    self-invalidates only after EVERY partition was served (the drop-driven
    accounting of `impl_execute_task.rs:97-112`)."""
    rng = np.random.default_rng(3)
    arrow = pa.table({"k": rng.integers(0, 40, 500), "v": rng.normal(size=500)})
    t = arrow_to_table(arrow)
    scan = MemoryScanExec([t], t.schema())

    w = Worker()
    key = TaskKey("q", 0, 0)
    store = TableStore()
    plan_obj = encode_plan(scan, store)
    for tid, tbl in store.tables.items():
        w.table_store.tables[tid] = tbl
    w.set_plan(key, plan_obj, task_count=1)

    got: dict[int, int] = {}
    for p, piece, _est in w.execute_task_partitions(
        key, ["k"], 4, 0, 2, chunk_rows=64
    ):
        got[p] = got.get(p, 0) + int(piece.num_rows)
    assert set(got) <= {0, 1}
    assert w.partitions_remaining(key) == 2  # half served, entry alive
    for p, piece, _est in w.execute_task_partitions(
        key, ["k"], 4, 2, 4, chunk_rows=64
    ):
        got[p] = got.get(p, 0) + int(piece.num_rows)
    assert sum(got.values()) == 500
    # all partitions served -> drop-driven invalidation
    assert w.registry.get(key) is None


def test_shuffle_partition_streams_match_bulk():
    """The static coordinator's partition-stream shuffle equals the
    adaptive coordinator's bulk regroup (same hash, different plane), and
    records the demux in stream_metrics."""
    from datafusion_distributed_tpu.runtime.coordinator import (
        AdaptiveCoordinator,
    )

    plan, arrow = sample_plan(3000, seed=9)
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=NT))
    cluster = InMemoryCluster(3)
    coord = Coordinator(resolver=cluster, channels=cluster)
    out = coord.execute(dplan).to_pandas()
    assert any(
        "partitions" in m for m in coord.stream_metrics.values()
    ), "partition-stream plane was not used for the shuffle"
    acoord = AdaptiveCoordinator(resolver=cluster, channels=cluster)
    exp = acoord.execute(dplan).to_pandas()
    np.testing.assert_array_equal(out["k"], exp["k"])
    np.testing.assert_allclose(out["sv"], exp["sv"], rtol=FLOAT_RTOL)
    np.testing.assert_array_equal(out["n"], exp["n"])


def test_overflow_retry_guard_budget(monkeypatch):
    """Retry guard: attempt 0 never blocks; a widened retry whose plan
    footprint exceeds DFTPU_RETRY_BYTES_BUDGET raises a DISTINCT error
    type (so the retry loops' overflow filter re-raises it instead of
    widening again) rather than letting dispatch hit an allocator
    failure."""
    import pytest

    from datafusion_distributed_tpu.schema import DataType, Field, Schema
    from datafusion_distributed_tpu.sql.context import (
        OverflowRetryAbandoned,
        _overflow_retry_guard,
    )

    monkeypatch.delenv("DFTPU_RETRY_BYTES_BUDGET", raising=False)

    class Fat:
        def schema(self):
            return Schema([Field("x", DataType.INT64, False)] * 16)

        def output_capacity(self):
            return 1 << 30

        def children(self):
            return []

        def collect(self, pred):
            return [self] if pred(self) else []

    _overflow_retry_guard(Fat(), 0, None)  # first attempt: no budget check
    with pytest.raises(OverflowRetryAbandoned, match="overflow-retry abandoned"):
        _overflow_retry_guard(Fat(), 1, RuntimeError("hash table overflow"))
    monkeypatch.setenv("DFTPU_RETRY_BYTES_BUDGET", "not-a-number")
    with pytest.raises(RuntimeError, match="DFTPU_RETRY_BYTES_BUDGET"):
        _overflow_retry_guard(Fat(), 1, RuntimeError("hash table overflow"))


def test_stage_shared_compiles_across_tasks():
    """Tasks of one stage reuse ONE traced program (plan/physical.py
    shared_cache): correctness is identical to per-task compiles and the
    hit counter shows every task after the first per (stage, shape) class
    skipped its XLA compile."""
    from datafusion_distributed_tpu.plan import physical as phys

    before = dict(phys._SHARED_STATS)
    qids_before = set(Worker._stage_compiles)
    try:
        plan, arrow = sample_plan(n=4096, seed=3)
        dplan = distribute_plan(plan, DistributedConfig(num_tasks=NT))
        coord = _cluster(2)
        out = coord.execute(dplan).to_pandas()
        exp = (
            arrow.to_pandas().groupby("k")
            .agg(sv=("v", "sum"), n=("v", "size")).reset_index()
            .sort_values("k").reset_index(drop=True)
        )
        # atol: a near-zero group sum (cancellation) has unbounded relative
        # error at f32 accumulation precision
        np.testing.assert_allclose(out["sv"], exp["sv"], rtol=FLOAT_RTOL,
                                   atol=1e-3)
        hits = phys._SHARED_STATS["hit"] - before["hit"]
        misses = phys._SHARED_STATS["miss"] - before["miss"]
        assert hits > 0, f"no shared-program hits (misses={misses})"
        # co-hosted workers share the class-level cache: one compile per
        # (stage, shape) class. Shape classes fragment (remainder-task leaf
        # shapes, single-task stages), so demand only that a meaningful
        # fraction of the multi-task stages' executions were compile-free.
        assert hits >= NT - 1, f"hits={hits} misses={misses}"
    finally:
        # class-level cache: don't leave this query's pinned programs
        # behind for the rest of the pytest process
        with Worker._stage_compiles_lock:
            for q in set(Worker._stage_compiles) - qids_before:
                Worker._stage_compiles.pop(q, None)


def test_stage_share_skipped_for_isolated_arms():
    """IsolatedArmExec bakes task_index into the traced program
    (plan/exchanges.py assigned_task branch) — such plans must bypass the
    shared cache."""
    from datafusion_distributed_tpu.plan.exchanges import IsolatedArmExec

    import uuid

    plan, arrow = sample_plan(n=512, seed=4)
    t = arrow_to_table(arrow)
    scan = MemoryScanExec([t], t.schema())
    arm = IsolatedArmExec(scan, assigned_task=0)
    w = Worker()
    qid = uuid.uuid4().hex  # unique: _stage_compiles is class-level
    try:
        data = TaskData(key=TaskKey(qid, 0, 0), plan=arm, task_count=2)
        cache, key = w._stage_compile_cache(data.key, data)
        assert cache is None and key is None
        # and a vanilla plan on the same worker does share, keyed by the
        # stage plan's structural fingerprint (plan/fingerprint.py)
        from datafusion_distributed_tpu.plan.fingerprint import prepare_plan

        data2 = TaskData(key=TaskKey(qid, 1, 0), plan=scan, task_count=2)
        cache2, key2 = w._stage_compile_cache(data2.key, data2)
        fp = prepare_plan(scan).fingerprint
        assert fp is not None
        assert cache2 is not None and key2 == (fp, 2, ())
    finally:
        with Worker._stage_compiles_lock:
            Worker._stage_compiles.pop(qid, None)
            from datafusion_distributed_tpu.plan.fingerprint import (
                prepare_plan as _pp,
            )

            Worker._stage_compiles.pop(("fp", _pp(scan).fingerprint), None)
