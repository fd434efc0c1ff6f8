"""Zero-copy data plane invariants (ISSUE 10).

The data plane used to move bytes between stages with eager device ops:
every chunk was a `slice_rows` copy, every consumer concat an eager
scatter, every shuffle regroup one gather PER destination, and the
TableStore was an unaccounted bare dict. The view-based rebuild
(runtime/codec.py TableStore + ops/table.py host views +
coordinator._shuffle_regroup host path) stages buffers once and hands out
views everywhere else.

Contracts pinned here:

- Buffer identity: put/get returns the staged object; `get_slice`/
  `put_view` and the worker partition plane hand out VIEWS sharing the
  staged buffers (np.shares_memory, one base buffer per regrouped output).
- Accounting: identity-dedup put (broadcast fan-out counts one buffer),
  refcounted release with alias promotion, thread-safe mutation, the
  legacy direct `tables[tid] = t` writes stay accounted, zero bytes/
  entries after queries (incl. chaos retry + membership churn).
- Byte identity: TPC-H q5/q9 results identical between
  `zero_copy = on` (default) and the copying plane, and vs single-node.
- Peak staged bytes under the chaos retry schedule do not regress vs the
  copying plane.
- Rate: the view chunk-plane (host slice + reassembly) beats the copying
  chunk-plane by >= 2x on a 1M-row stream.
"""

import os
import threading

import numpy as np
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.table import (
    _base_buffer,
    concat_tables,
    host_view,
    is_host_backed,
    slice_view,
    zero_copy_enabled,
)
from datafusion_distributed_tpu.plan.physical import MemoryScanExec
from datafusion_distributed_tpu.runtime.chaos import (
    one_crash_per_stage,
    wrap_cluster,
)
from datafusion_distributed_tpu.runtime.codec import (
    TableStore,
    decode_table,
    encode_plan,
    encode_table,
)
from datafusion_distributed_tpu.runtime.coordinator import (
    Coordinator,
    InMemoryCluster,
    _shuffle_regroup,
)
from datafusion_distributed_tpu.runtime.observability import (
    ObservabilityService,
)
from datafusion_distributed_tpu.runtime.tracing import table_nbytes
from datafusion_distributed_tpu.runtime.worker import TaskKey, Worker

CHAOS_SEED = int(os.environ.get("DFTPU_CHAOS_SEED", "20260803"))
FAST = {"task_retry_backoff_s": 0.001}


@pytest.fixture(autouse=True)
def _no_zero_copy_env_override(monkeypatch):
    """DFTPU_ZERO_COPY takes priority over session config; an exported
    override would silently collapse this module's copy-vs-view A/B
    comparisons into view-vs-view (vacuous gates)."""
    monkeypatch.delenv("DFTPU_ZERO_COPY", raising=False)

TPCH_Q5 = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey
  and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey
  and n_regionkey = r_regionkey
  and r_name = 'ASIA'
  and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1995-01-01'
group by n_name
order by revenue desc
"""

TPCH_Q9 = """
select nation, o_year, sum(amount) as sum_profit from (
  select n_name as nation, extract(year from o_orderdate) as o_year,
         l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity
           as amount
  from part, supplier, lineitem, partsupp, orders, nation
  where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
    and ps_partkey = l_partkey and p_partkey = l_partkey
    and o_orderkey = l_orderkey and s_nationkey = n_nationkey
    and p_name like '%green%'
) as profit group by nation, o_year order by nation, o_year desc
"""


def _table(rows=4096, seed=0, strings=False):
    rng = np.random.default_rng(seed)
    cols = {
        "k": rng.integers(0, 64, rows),
        "v": rng.normal(size=rows),
    }
    if strings:
        cols["s"] = pa.array(rng.choice(["aa", "bb", "cc"], rows))
    return arrow_to_table(pa.table(cols))


@pytest.fixture(scope="module")
def tpch_ctx():
    from datafusion_distributed_tpu.data.tpchgen import gen_tpch
    from datafusion_distributed_tpu.sql.context import SessionContext

    ctx = SessionContext()
    ctx.config.distributed_options["bytes_per_task"] = 1  # force fan-out
    for name, arrow in gen_tpch(sf=0.002, seed=7).items():
        ctx.register_arrow(name, arrow)
    return ctx


def _run(ctx, sql, cluster, **opts):
    df = ctx.sql(sql)
    coord = Coordinator(resolver=cluster, channels=cluster,
                        config_options={**FAST, **opts})
    out = df._strip_quals(
        df.collect_coordinated_table(coordinator=coord, num_tasks=4)
    ).to_pandas()
    return out, coord


def _assert_no_leaks(cluster: InMemoryCluster):
    for w in cluster.workers.values():
        assert not w.table_store.tables, (
            f"{w.url} leaked TableStore entries"
        )
        assert w.table_store.nbytes() == 0, (
            f"{w.url} accounting leaked: {w.table_store.stats()}"
        )
        assert len(w.registry) == 0, f"{w.url} leaked registry entries"


def _assert_frames_identical(got, base, label=""):
    assert list(got.columns) == list(base.columns)
    for col in base.columns:
        np.testing.assert_array_equal(
            got[col].to_numpy(), base[col].to_numpy(),
            err_msg=f"{label}.{col} diverged between planes",
        )


# ---------------------------------------------------------------------------
# TableStore: identity, views, accounting, thread safety
# ---------------------------------------------------------------------------


def test_put_get_buffer_identity_and_accounting():
    t = _table(strings=True)
    s = TableStore()
    tid = s.put(t)
    assert s.get(tid) is t  # in-process staging is by reference
    nb = table_nbytes(t)
    assert s.nbytes() == nb and s.entry_nbytes(tid) == nb
    assert s.stats()["entries"] == 1
    s.remove([tid])
    assert s.tables == {} and s.nbytes() == 0
    assert s.peak_nbytes == nb  # high-water mark survives release


def test_identity_dedup_counts_broadcast_once():
    """Staging the SAME object per consumer (broadcast fan-out, retry
    re-ship) registers aliases — one buffer's bytes, N entries."""
    t = _table()
    s = TableStore()
    nb = table_nbytes(t)
    tids = [s.put(t) for _ in range(4)]
    st = s.stats()
    assert st["entries"] == 4 and st["views"] == 3 and st["dedup_hits"] == 3
    assert s.nbytes() == nb  # counted ONCE
    assert all(s.entry_nbytes(tid) == nb for tid in tids)
    # releasing the owner promotes an alias: bytes stay accounted until
    # the LAST reference drops
    s.remove(tids[:1])
    assert s.nbytes() == nb
    s.remove(tids[1:3])
    assert s.nbytes() == nb
    s.remove(tids[3:])
    assert s.nbytes() == 0 and s.tables == {}


def test_get_slice_and_put_view_share_buffers():
    t = _table()
    s = TableStore()
    tid = s.put(t)
    base = np.asarray(t.columns[0].data)
    v = s.get_slice(tid, 100, 500)
    assert int(v.num_rows) == 500
    assert np.shares_memory(v.columns[0].data, base)
    np.testing.assert_array_equal(
        np.asarray(v.columns[0].data), base[100:600]
    )
    vid = s.put_view(tid, lo=100, count=500)
    vt = s.get(vid)
    assert np.shares_memory(vt.columns[0].data, base)
    assert s.nbytes() == table_nbytes(t)  # view adds ZERO owned bytes
    assert s.stats()["views"] == 1
    s.remove([tid, vid])
    assert s.nbytes() == 0 and s.tables == {}


def test_direct_dict_mutation_stays_accounted():
    """Legacy call sites (wire receive, cluster teardown) write
    `store.tables` directly; the mapping routes through accounting —
    through EVERY mutator, not just __setitem__."""
    t = _table()
    s = TableStore()
    s.tables["abc"] = t
    assert s.nbytes() == table_nbytes(t)
    s.tables["abc"] = t  # replacement re-accounts, no double count
    assert s.nbytes() == table_nbytes(t)
    s.tables.update({"def": t})
    assert s.stats()["entries"] == 2
    s.tables.setdefault("ghi", t)
    assert s.stats()["entries"] == 3
    tid, _val = s.tables.popitem()
    assert tid == "ghi" and s.stats()["entries"] == 2
    s.tables.clear()
    assert s.nbytes() == 0 and s.stats()["entries"] == 0


def test_repartition_releases_previous_staged_slices():
    """A consumer re-pulling under a NEW (keys, P) spec (adaptive task
    counts, retried consumers) must not pin or double-count the previous
    regrouped buffer's staged slices."""
    w = Worker(url="mem://dp-respec")
    t = _table(rows=2048)
    plan_obj = encode_plan(MemoryScanExec([t], t.schema()), w.table_store)
    key = TaskKey("dpr", 0, 0)
    w.set_plan(key, plan_obj, 1, ttl=3600.0)  # TTL: no self-invalidation
    list(w.execute_task_partitions(key, ["k"], 4, 0, 4,
                                   per_dest_capacity=2048))
    data = w.registry.get(key)
    first = list(data.staged_partition_ids)
    n1 = w.table_store.stats()["entries"]
    list(w.execute_task_partitions(key, ["k"], 2, 0, 2,
                                   per_dest_capacity=2048))
    assert data.staged_partition_ids != first
    # the first spec's slice ids were released, not accumulated
    assert all(tid not in w.table_store.tables for tid in first)
    assert w.table_store.stats()["entries"] <= n1
    w.release_task(key)
    assert w.table_store.tables == {} and w.table_store.nbytes() == 0


def test_store_thread_safety():
    """put/remove race from serving-tier + stage-fan-out threads: the old
    bare dict lost updates; the store must end exactly empty."""
    s = TableStore()
    tables = [_table(rows=64, seed=i) for i in range(8)]
    errors = []

    def churn(i):
        try:
            for _ in range(200):
                tid = s.put(tables[i % len(tables)])
                v = s.put_view(tid)
                s.remove([v, tid])
        except Exception as e:  # pragma: no cover - failure detail
            errors.append(e)

    threads = [threading.Thread(target=churn, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert s.tables == {} and s.nbytes() == 0
    assert s.stats()["entries"] == 0


# ---------------------------------------------------------------------------
# host views: slice, concat, regroup
# ---------------------------------------------------------------------------


def test_host_view_and_slice_view_zero_copy():
    t = _table()
    h = host_view(t)
    assert is_host_backed(h)
    # CPU backend: the host rebind itself is zero-copy
    assert np.shares_memory(h.columns[0].data, np.asarray(t.columns[0].data))
    v = slice_view(h, 64, 256)
    assert int(v.num_rows) == 256 and v.capacity == 256
    assert np.shares_memory(v.columns[0].data, h.columns[0].data)


def test_contiguous_chunks_concat_to_a_view():
    t = _table(rows=1000)
    h = host_view(t)
    chunks = [slice_view(h, lo, 250) for lo in range(0, 1000, 250)]
    out = concat_tables(chunks, capacity=1024)
    assert int(out.num_rows) == 1000 and out.capacity == 1024
    # reassembly of contiguous views is a VIEW of the base buffer
    assert np.shares_memory(out.columns[0].data, h.columns[0].data)
    np.testing.assert_array_equal(
        np.asarray(out.columns[0].data[:1000]),
        np.asarray(t.columns[0].data[:1000]),
    )


def test_host_concat_matches_device_concat():
    a, b = _table(rows=300, seed=1, strings=True), _table(
        rows=200, seed=2, strings=True
    )
    dev = concat_tables([a, b], capacity=512)  # device path (jax-backed)
    hst = concat_tables([host_view(a), host_view(b)], capacity=512)
    assert is_host_backed(hst)
    da, ha = dev.to_numpy(), hst.to_numpy()
    for col in da:
        np.testing.assert_array_equal(np.asarray(da[col]),
                                      np.asarray(ha[col]), err_msg=col)


def test_shuffle_regroup_view_matches_copy():
    outs = [_table(rows=1024, seed=i) for i in range(2)]
    copy = _shuffle_regroup(outs, ["k"], 4, 1024, zero_copy=False)
    view = _shuffle_regroup(outs, ["k"], 4, 1024, zero_copy=True)
    assert len(copy) == len(view) == 4
    for j in range(4):
        c, v = copy[j].to_numpy(), view[j].to_numpy()
        assert int(copy[j].num_rows) == int(view[j].num_rows)
        for col in c:  # same rows, same ORDER (stable bucketing)
            np.testing.assert_array_equal(
                np.asarray(c[col]), np.asarray(v[col]),
                err_msg=f"partition {j}.{col}",
            )


def test_regroup_exact_slices_share_one_buffer():
    """The peer partition plane: per-destination slices of one producer
    output are views of ONE destination-major buffer."""
    out = _table(rows=2048)
    slices = _shuffle_regroup([out], ["k"], 4, 2048, zero_copy=True,
                              exact=True)
    nonzero = [s for s in slices if int(s.num_rows)]
    assert len(nonzero) >= 2
    bases = {id(_base_buffer(s.columns[0].data)) for s in nonzero}
    assert len(bases) == 1, "per-dest slices must share one staged buffer"
    total = sum(int(s.num_rows) for s in slices)
    assert total == 2048  # partition of the whole output


# ---------------------------------------------------------------------------
# worker partition plane: views end-to-end + drop-driven release
# ---------------------------------------------------------------------------


def test_worker_partition_chunks_are_views_and_release_on_drop():
    w = Worker(url="mem://dp-w0")
    t = _table(rows=4096)
    plan_obj = encode_plan(MemoryScanExec([t], t.schema()), w.table_store)
    key = TaskKey("dpq", 0, 0)
    w.set_plan(key, plan_obj, 1)
    gen = w.execute_task_partitions(key, ["k"], 4, 0, 4,
                                    per_dest_capacity=4096)
    p0, chunk0, _est = next(gen)
    data = w.registry.get(key)
    slices = data.partition_slices
    assert all(is_host_backed(s) for s in slices)
    nonzero = [s for s in slices if int(s.num_rows)]
    bases = {id(_base_buffer(s.columns[0].data)) for s in nonzero}
    assert len(bases) == 1, "partition slices must view one buffer"
    # the chunk crossing the (in-process) wire IS a view of the staged
    # partition slice — provably copy-free producer output -> consumer
    assert np.shares_memory(chunk0.columns[0].data,
                            slices[p0].columns[0].data)
    # the partition slices are registered in the store (byte-accounted)
    assert w.table_store.nbytes() > 0
    list(gen)  # drain every partition
    # drop-driven release: last partition served -> entry self-invalidated
    # -> staged slices (input AND partitions) released, accounting at zero
    assert w.table_store.tables == {}
    assert w.table_store.nbytes() == 0
    assert len(w.registry) == 0


# ---------------------------------------------------------------------------
# encode/decode: no double copy, capacity passthrough
# ---------------------------------------------------------------------------


def test_encode_table_single_buffer_and_decode_capacity_passthrough():
    t = _table(rows=1000, strings=True)
    payload = encode_table(t)
    # BufferOutputStream + memoryview: no BytesIO+getvalue duplication
    assert isinstance(payload, memoryview)
    back = decode_table(payload, capacity=int(t.capacity))
    assert back.capacity == t.capacity and int(back.num_rows) == 1000
    # capacity == live rows: the no-pad fast path must still be exact
    exact = decode_table(payload, capacity=1000)
    assert exact.capacity == 1000 and int(exact.num_rows) == 1000
    a, b = t.to_numpy(), exact.to_numpy()
    for col in a:
        np.testing.assert_array_equal(np.asarray(a[col]),
                                      np.asarray(b[col]), err_msg=col)


# ---------------------------------------------------------------------------
# config gate + observability surface
# ---------------------------------------------------------------------------


def test_zero_copy_knob_parses_and_gates():
    from datafusion_distributed_tpu.sql.context import SessionConfig

    cfg = SessionConfig()
    cfg.set_option("distributed.zero_copy", "off")
    assert cfg.distributed_options["zero_copy"] is False
    assert zero_copy_enabled(cfg.distributed_options) is False
    cfg.set_option("distributed.zero_copy", "on")
    assert zero_copy_enabled(cfg.distributed_options) is True
    assert zero_copy_enabled(None) is True  # default ON


def test_observability_and_console_surface_staged_bytes():
    from datafusion_distributed_tpu.console import Console

    cluster = InMemoryCluster(2)
    w = next(iter(cluster.workers.values()))
    t = _table()
    tid = w.table_store.put(t)
    obs = ObservabilityService(cluster, cluster)
    dp = obs.get_data_plane()
    assert dp["nbytes"] == table_nbytes(t) and dp["entries"] == 1
    assert w.url in dp["workers"]
    frame = Console(cluster, cluster).render_frame()
    assert "data plane" in frame and "staged" in frame
    w.table_store.remove([tid])
    assert obs.get_data_plane()["nbytes"] == 0


# ---------------------------------------------------------------------------
# TPC-H: byte identity, chaos leak/peak gates
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qname,sql", [("q5", TPCH_Q5), ("q9", TPCH_Q9)])
def test_tpch_byte_identical_view_vs_copy_plane(tpch_ctx, qname, sql):
    single = tpch_ctx.sql(sql)
    base = single._strip_quals(single.collect_table()).to_pandas()
    cluster = InMemoryCluster(4)
    on, _ = _run(tpch_ctx, sql, cluster, zero_copy=True)
    _assert_no_leaks(cluster)
    off, _ = _run(tpch_ctx, sql, cluster, zero_copy=False)
    _assert_no_leaks(cluster)
    # the acceptance contract: the view plane's rows are BYTE-identical
    # to the copying plane's (same partition order, same pad semantics)
    _assert_frames_identical(on, off, f"{qname}[view-vs-copy]")
    # and numerically the distributed result matches single-node (exact
    # equality is not the contract here: a distributed sum reassociates
    # float additions vs the single-node order)
    for col in base.columns:
        a, b = on[col].to_numpy(), base[col].to_numpy()
        if np.issubdtype(np.asarray(b).dtype, np.floating):
            # f32 accumulation over reassociated partial sums: a few ulps
            np.testing.assert_allclose(a, b, rtol=5e-5,
                                       err_msg=f"{qname}.{col}")
        else:
            np.testing.assert_array_equal(a, b, err_msg=f"{qname}.{col}")


def test_q5_chaos_retry_churn_no_leaks_and_identical(tpch_ctx):
    base_cluster = InMemoryCluster(4)
    base, _ = _run(tpch_ctx, TPCH_Q5, base_cluster, zero_copy=True)
    cluster = InMemoryCluster(4)
    chaos = wrap_cluster(cluster, one_crash_per_stage(CHAOS_SEED))
    out, _ = _run(tpch_ctx, TPCH_Q5, chaos, zero_copy=True)
    _assert_frames_identical(out, base, "q5[chaos]")
    # refcount release under retry: every re-staged/aliased slice freed
    _assert_no_leaks(cluster)


def test_q5_peak_staged_bytes_no_regression_under_chaos(tpch_ctx):
    """The chaos retry schedule re-stages slices; with the view plane the
    re-ships alias existing buffers and per-dest slices are views, so the
    summed high-water mark must not exceed the copying plane's."""

    def peak(zero_copy):
        cluster = InMemoryCluster(4)
        chaos = wrap_cluster(cluster, one_crash_per_stage(CHAOS_SEED))
        out, _ = _run(tpch_ctx, TPCH_Q5, chaos, zero_copy=zero_copy,
                      stage_parallelism=1)  # deterministic staging order
        _assert_no_leaks(cluster)
        return sum(
            w.table_store.peak_nbytes for w in cluster.workers.values()
        ), out

    peak_off, out_off = peak(False)
    peak_on, out_on = peak(True)
    _assert_frames_identical(out_on, out_off, "q5[peak-arms]")
    assert peak_on <= peak_off, (
        f"view plane peak {peak_on} exceeds copying plane {peak_off}"
    )


# ---------------------------------------------------------------------------
# rate gate: view chunk-plane >= 2x the copying chunk-plane
# ---------------------------------------------------------------------------


def test_chunk_plane_rate_at_least_2x():
    import time

    import jax

    rows, chunk = 1 << 20, 1 << 16
    t = _table(rows=rows, seed=3)
    width = sum(int(c.data.dtype.itemsize) for c in t.columns)
    nbytes = rows * width

    def copy_plane():
        chunks = [t.slice_rows(lo, chunk) for lo in range(0, rows, chunk)]
        out = concat_tables(chunks, capacity=rows)
        jax.block_until_ready(out.columns[0].data)
        return out

    def view_plane():
        h = host_view(t)
        chunks = [slice_view(h, lo, chunk) for lo in range(0, rows, chunk)]
        out = concat_tables(chunks, capacity=rows)
        np.asarray(out.columns[0].data)
        return out

    def best(fn, repeats=3):
        fn()  # warm (compile/caches)
        b = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            b = min(b, time.perf_counter() - t0)
        return b

    t_copy, t_view = best(copy_plane), best(view_plane)
    speedup = t_copy / max(t_view, 1e-9)
    gbps_view = nbytes / max(t_view, 1e-9) / 1e9
    assert speedup >= 2.0, (
        f"view plane only {speedup:.2f}x over the copying plane "
        f"({gbps_view:.2f} GB/s)"
    )
    # results identical between the two planes
    a, b = copy_plane().to_numpy(), view_plane().to_numpy()
    for col in a:
        np.testing.assert_array_equal(np.asarray(a[col]),
                                      np.asarray(b[col]), err_msg=col)
