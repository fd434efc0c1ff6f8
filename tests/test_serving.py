"""Multi-query serving tier (runtime/serving.py).

Contracts pinned here:

- Async frontend: submit/status/result/cancel lifecycle; N concurrent
  clients running mixed TPC-H queries produce BYTE-IDENTICAL results vs
  sequential execution — including under a seeded chaos + membership-
  churn schedule — with zero leaked TableStore slices once every handle
  resolves.
- Global cross-query scheduler: one bounded slot pool serves all
  admitted queries; fair-share stride scheduling (pass = accumulated
  stage wall) lets a cheap query's stages overtake a heavy query's;
  FIFO mode reproduces arrival order; in-flight stages never exceed the
  slot budget; selection is deterministic given the seed.
- Admission control: `SET distributed.admission_budget_bytes` /
  `max_concurrent_queries` queue (FIFO within priority class, higher
  class first) instead of over-committing; queued queries admit as
  capacity frees; a query wider than the whole budget still runs alone.
- Prepared statements: `ctx.prepare(sql)` bindings ride the literal-
  hoist + fingerprint machinery — ZERO new XLA traces across parameter
  variations on the serving (coordinated) path after warm-up (the
  recompile-budget gate extended to serving).
- Bookkeeping bounds: MetricsStore LRU never evicts a running query;
  query-scoped chaos state replays one schedule per query and sweeps on
  completion; query ids and TableStore slice ids are uuid-unique under
  any concurrency.
"""

import datetime
import os
import threading
import time

import numpy as np
import pytest

from datafusion_distributed_tpu.plan import physical as phys
from datafusion_distributed_tpu.runtime.chaos import (
    FaultPlan,
    FaultSpec,
    MembershipEvent,
    wrap_cluster,
)
from datafusion_distributed_tpu.runtime.coordinator import (
    Coordinator,
    DynamicCluster,
    InMemoryCluster,
)
from datafusion_distributed_tpu.runtime.errors import TaskCancelledError
from datafusion_distributed_tpu.runtime.metrics import MetricsStore
from datafusion_distributed_tpu.runtime.serving import (
    CANCELLED,
    DONE,
    QUEUED,
    RUNNING,
    GlobalStageScheduler,
    ServingSession,
)
from datafusion_distributed_tpu.runtime.worker import TaskKey

CHAOS_SEED = int(os.environ.get("DFTPU_CHAOS_SEED", "20260803"))

# Inlined TPC-H texts (the reference checkout's testdata/ is absent in
# this container). q1/q6 are the CHEAP serving mix; q3 is the bushy
# multi-join whose sibling stages exercise the cross-query scheduler.
TPCH_Q1 = """
select l_returnflag, l_linestatus,
       sum(l_quantity) as sum_qty,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       count(*) as count_order
from lineitem
where l_shipdate <= date '1998-09-02'
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

TPCH_Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07
  and l_quantity < 24
"""

TPCH_Q3 = """
select l_orderkey,
       sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING'
  and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

Q6_TEMPLATE = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= $d1
  and l_shipdate < $d2
  and l_discount between $lo and $hi
  and l_quantity < $qty
"""

MIX = {"q1": TPCH_Q1, "q3": TPCH_Q3, "q6": TPCH_Q6}


@pytest.fixture(scope="module")
def tpch_ctx():
    from datafusion_distributed_tpu.data.tpchgen import gen_tpch
    from datafusion_distributed_tpu.sql.context import SessionContext

    ctx = SessionContext()
    ctx.config.distributed_options["bytes_per_task"] = 1  # force fan-out
    ctx.config.distributed_options["broadcast_joins"] = False
    ctx.config.distributed_options["task_retry_backoff_s"] = 0.001
    for name, arrow in gen_tpch(sf=0.002, seed=7).items():
        ctx.register_arrow(name, arrow)
    return ctx


@pytest.fixture(scope="module")
def sequential_reference(tpch_ctx):
    """name -> pandas frame from plain sequential coordinated runs."""
    out = {}
    for name, sql in MIX.items():
        # the arrow conversion path (collect_coordinated), matching what
        # QueryHandle.result() returns — raw-table to_pandas would leave
        # date columns as int32 day counts and never compare equal
        out[name] = tpch_ctx.sql(sql).collect_coordinated(
            coordinator=_coord(InMemoryCluster(4)), num_tasks=4
        ).to_pandas()
    return out


def _coord(cluster, **opts):
    return Coordinator(
        resolver=cluster, channels=cluster,
        config_options={"bytes_per_task": 1, "broadcast_joins": False,
                        "task_retry_backoff_s": 0.001, **opts},
    )


def _assert_no_leaks(cluster):
    for w in cluster.workers.values():
        assert not w.table_store.tables, (
            f"{w.url} leaked TableStore entries"
        )
        assert len(w.registry) == 0, f"{w.url} leaked registry entries"


def _assert_frames_identical(got, base, label=""):
    assert list(got.columns) == list(base.columns), label
    for col in base.columns:
        np.testing.assert_array_equal(
            got[col].to_numpy(), base[col].to_numpy(),
            err_msg=f"{label}.{col} diverged from sequential execution",
        )


def _delay_cluster(workers=4, delay_s=0.05, seed=CHAOS_SEED):
    """In-memory cluster with a uniform injected execute delay — the
    stand-in for device/DCN latency that makes scheduling effects
    observable on a small box."""
    return wrap_cluster(InMemoryCluster(workers), FaultPlan(seed, [
        FaultSpec(site="execute", kind="delay", delay_s=delay_s, rate=1.0),
    ], query_scoped=True))


# ---------------------------------------------------------------------------
# async frontend
# ---------------------------------------------------------------------------


def test_handle_lifecycle(tpch_ctx, sequential_reference):
    with ServingSession(tpch_ctx, num_workers=4, num_tasks=4) as srv:
        h = srv.submit(TPCH_Q6)
        out = h.result(timeout=300)
        assert h.status() == DONE and h.done()
        assert h.wall_s() is not None and h.queue_wait_s() is not None
        _assert_frames_identical(
            out.to_pandas(), sequential_reference["q6"], "q6"
        )
        # uuid-unique handle ids under repeated submission
        h2 = srv.submit(TPCH_Q6)
        h2.result(timeout=300)
        assert h.query_id != h2.query_id
    _assert_no_leaks(srv.cluster)


def test_submit_rejects_non_query(tpch_ctx):
    with ServingSession(tpch_ctx, num_workers=2) as srv:
        with pytest.raises(ValueError, match="SELECT"):
            srv.submit("set distributed.stage_parallelism = 2")


def test_concurrent_mixed_byte_identical(tpch_ctx, sequential_reference):
    """8 client threads, closed loop, mixed cheap/bushy queries: every
    result byte-identical to sequential execution, zero leaks after all
    handles resolve."""
    clients, iters = 8, 2
    results: dict = {}
    errors: list = []
    with ServingSession(tpch_ctx, num_workers=4, num_tasks=4,
                        max_concurrent_queries=8) as srv:
        def client(ci: int) -> None:
            names = ["q1", "q6", "q3"]
            try:
                for it in range(iters):
                    name = names[(ci + it) % len(names)]
                    h = srv.submit(MIX[name])
                    tbl = h.result(timeout=600)
                    results[(ci, it, name)] = tbl
            except BaseException as e:  # surfaced below
                errors.append((ci, e))

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not errors, errors
        st = srv.stats()
        assert st["admitted_total"] == clients * iters
        assert st["completed"][DONE] == clients * iters
    for (ci, it, name), tbl in results.items():
        _assert_frames_identical(
            tbl.to_pandas(), sequential_reference[name],
            f"client{ci}/iter{it}/{name}",
        )
    _assert_no_leaks(srv.cluster)


def test_concurrent_under_chaos_and_churn(tpch_ctx, sequential_reference):
    """Concurrent serving over a DynamicCluster wrapped in a seeded
    chaos + membership-churn schedule (transient faults, a leave, a
    join): results stay byte-identical, the per-query chaos state is
    swept as handles resolve, no leaked slices."""
    cluster = DynamicCluster(4)
    urls = cluster.get_urls()
    plan = FaultPlan(CHAOS_SEED, [
        FaultSpec(site="execute", kind="transport", rate=0.1),
        FaultSpec(site="set_plan", kind="transport", rate=0.05),
    ], membership=[
        MembershipEvent("leave", urls[3], site="execute", nth_call=5),
        MembershipEvent("join", "mem://joiner-srv", site="set_plan",
                        nth_call=12),
    ], query_scoped=True)
    chaos = wrap_cluster(cluster, plan)
    tpch_ctx.config.distributed_options["max_task_retries"] = 8
    try:
        with ServingSession(tpch_ctx, cluster=chaos, num_tasks=4,
                            max_concurrent_queries=6) as srv:
            handles = [
                srv.submit(MIX[name])
                for name in ("q1", "q6", "q3", "q6", "q1", "q3")
            ]
            for h, name in zip(handles,
                               ("q1", "q6", "q3", "q6", "q1", "q3")):
                _assert_frames_identical(
                    h.result(timeout=600).to_pandas(),
                    sequential_reference[name], f"chaos/{name}",
                )
    finally:
        tpch_ctx.config.distributed_options.pop("max_task_retries", None)
    kinds = {f["kind"] for f in plan.fired}
    assert "membership_leave" in kinds and "membership_join" in kinds
    assert urls[3] not in cluster.get_urls()
    assert "mem://joiner-srv" in cluster.get_urls()
    # per-query chaos call state swept on completion (on_query_end)
    assert not plan._calls, list(plan._calls)[:4]
    _assert_no_leaks(cluster)


def test_cancel_queued_and_running(tpch_ctx):
    chaos = _delay_cluster(workers=2, delay_s=0.2)
    with ServingSession(tpch_ctx, cluster=chaos, num_tasks=2,
                        max_concurrent_queries=1) as srv:
        h1 = srv.submit(TPCH_Q6)
        h2 = srv.submit(TPCH_Q6)  # queued behind h1
        assert h2.status() == QUEUED
        assert h2.cancel()
        assert h2.status() == CANCELLED
        with pytest.raises(TaskCancelledError):
            h2.result_table(timeout=5)
        # h1 is mid-execution (injected delay): cancel reaches the
        # coordinator's dispatch/execute checkpoints
        assert h1.cancel()
        with pytest.raises(TaskCancelledError):
            h1.result_table(timeout=60)
        assert h1.status() == CANCELLED
        srv.drain(timeout=60)
    # cancelled mid-flight work released its staged slices
    _assert_no_leaks(chaos.inner)


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_budget_queues_behind_footprint(tpch_ctx):
    from datafusion_distributed_tpu.planner.statistics import (
        plan_device_bytes,
    )

    est = plan_device_bytes(tpch_ctx.sql(TPCH_Q3).physical_plan())
    assert est > 0
    chaos = _delay_cluster(workers=4, delay_s=0.15)
    with ServingSession(tpch_ctx, cluster=chaos, num_tasks=4,
                        admission_budget_bytes=est * 1.5,
                        max_concurrent_queries=8) as srv:
        h1 = srv.submit(TPCH_Q3)
        h2 = srv.submit(TPCH_Q3)  # would exceed the byte budget -> queue
        assert h1.status() == RUNNING
        assert h2.status() == QUEUED
        st = srv.stats()
        assert st["active"] == 1 and st["queued"] == 1
        assert st["in_use_bytes"] == h1.est_bytes == est
        h1.result(timeout=600)
        out2 = h2.result(timeout=600)  # admitted once h1 released bytes
        assert h2.status() == DONE and out2.num_rows >= 0
    _assert_no_leaks(chaos.inner)


def test_admission_oversized_query_runs_alone(tpch_ctx):
    """A query whose estimate exceeds the WHOLE budget still runs when
    the pool is empty (no permanent starvation)."""
    with ServingSession(tpch_ctx, num_workers=2, num_tasks=2,
                        admission_budget_bytes=1.0) as srv:
        h = srv.submit(TPCH_Q6)
        h.result(timeout=300)
        assert h.status() == DONE


def test_max_concurrent_queries_bound(tpch_ctx, sequential_reference):
    chaos = _delay_cluster(workers=4, delay_s=0.1)
    peak = [0]
    with ServingSession(tpch_ctx, cluster=chaos, num_tasks=4,
                        max_concurrent_queries=2) as srv:
        handles = [srv.submit(TPCH_Q6) for _ in range(5)]

        def watch():
            while any(not h.done() for h in handles):
                peak[0] = max(peak[0], srv.stats()["active"])
                time.sleep(0.01)

        w = threading.Thread(target=watch, daemon=True)
        w.start()
        for h in handles:
            _assert_frames_identical(
                h.result(timeout=600).to_pandas(),
                sequential_reference["q6"], "bounded/q6",
            )
        w.join(timeout=10)
    assert peak[0] <= 2, f"admission exceeded max_concurrent: {peak[0]}"


def test_priority_class_admission_order(tpch_ctx):
    chaos = _delay_cluster(workers=2, delay_s=0.2)
    with ServingSession(tpch_ctx, cluster=chaos, num_tasks=2,
                        max_concurrent_queries=1) as srv:
        h1 = srv.submit(TPCH_Q6)           # running
        h_lo = srv.submit(TPCH_Q6, priority=0)
        h_hi = srv.submit(TPCH_Q6, priority=5)
        for h in (h1, h_lo, h_hi):
            h.result(timeout=600)
        # the higher class left the queue first even though it arrived
        # later (FIFO holds only WITHIN a class)
        assert h_hi.admitted_s < h_lo.admitted_s


def test_close_resolves_backlog_gracefully(tpch_ctx):
    """Default close() stops ACCEPTING queries but the already-queued
    backlog still admits and resolves — no handle is ever stranded with
    a forever-blocking result()."""
    chaos = _delay_cluster(workers=2, delay_s=0.05)
    srv = ServingSession(tpch_ctx, cluster=chaos, num_tasks=2,
                         max_concurrent_queries=1)
    handles = [srv.submit(TPCH_Q6) for _ in range(3)]
    srv.close()  # cancel_pending=False: graceful
    for h in handles:
        h.result(timeout=300)
        assert h.status() == DONE
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(TPCH_Q6)


def test_stage_parallelism_bounds_query_under_global_pool(tpch_ctx):
    """`SET distributed.stage_parallelism` keeps its memory-control
    meaning under the serving tier: one query's in-flight stages on the
    GLOBAL pool never exceed the per-query budget."""
    from datafusion_distributed_tpu.runtime.serving import _QueryPool

    class CountingScheduler:
        def __init__(self):
            self.in_flight = 0
            self.peak = 0
            self._lock = threading.Lock()

        def submit(self, qid, fn, cost_hint=0):
            import concurrent.futures as cf

            fut = cf.Future()

            def run():
                with self._lock:
                    self.in_flight += 1
                    self.peak = max(self.peak, self.in_flight)
                try:
                    fut.set_result(fn())
                except BaseException as e:
                    fut.set_exception(e)
                finally:
                    with self._lock:
                        self.in_flight -= 1

            threading.Thread(target=run, daemon=True).start()
            return fut

    sched = CountingScheduler()
    cluster = InMemoryCluster(4)
    coord = _coord(cluster, stage_parallelism=1)
    coord.stage_pool = _QueryPool(sched, "q-bounded")  # type: ignore
    df = tpch_ctx.sql(TPCH_Q3)
    coord.execute(df.distributed_plan(
        4, config=df._seeded_host_config(4), coordinator=coord
    ))
    # root stage runs alone after materialization; the bound applies to
    # the DAG phase — with stage_parallelism=1 nothing overlaps
    assert sched.peak == 1, (
        f"{sched.peak} concurrent stages despite stage_parallelism=1"
    )
    _assert_no_leaks(cluster)


def test_serving_knobs_via_set(tpch_ctx):
    """SET distributed.* serving knobs validate at SET time and reach
    admission decisions live."""
    tpch_ctx.sql("set distributed.max_concurrent_queries = 3")
    tpch_ctx.sql("set distributed.admission_budget_bytes = 123456789")
    try:
        srv = ServingSession(tpch_ctx, num_workers=2)
        try:
            assert srv._max_concurrent() == 3
            assert srv._budget_bytes() == 123456789.0
        finally:
            srv.close()
        with pytest.raises(ValueError, match="max_concurrent_queries"):
            tpch_ctx.sql("set distributed.max_concurrent_queries = 0")
        with pytest.raises(ValueError, match="admission_budget_bytes"):
            # the SET lexer has no unary minus; the scope handler still
            # rejects a negative budget set programmatically
            tpch_ctx.config.set_option(
                "distributed.admission_budget_bytes", -1
            )
        # scheduler knobs validate at SET time too
        with pytest.raises(ValueError):
            tpch_ctx.config.set_option(
                "distributed.serving_stage_slots", "x"
            )
        tpch_ctx.sql("set distributed.fair_share = false")
        assert tpch_ctx.config.distributed_options["fair_share"] is False
        tpch_ctx.config.distributed_options.pop("fair_share", None)
    finally:
        tpch_ctx.config.distributed_options.pop(
            "max_concurrent_queries", None)
        tpch_ctx.config.distributed_options.pop(
            "admission_budget_bytes", None)


# ---------------------------------------------------------------------------
# global cross-query scheduler
# ---------------------------------------------------------------------------


def _run_all(sched, jobs):
    futs = [sched.submit(qid, fn) for qid, fn in jobs]
    for f in futs:
        f.result(timeout=30)
    return futs


def test_fair_share_stride_overtakes_heavy():
    """After a heavy query accumulated stage wall, a cheap query's
    pending stage wins the next slot even though the heavy query's stage
    arrived first."""
    sched = GlobalStageScheduler(slots=1, fair_share=True, seed=1)
    try:
        sched.register_query("heavy")
        sched.register_query("cheap")
        started = threading.Event()

        def blocker():
            started.set()
            time.sleep(0.08)

        b = sched.submit("heavy", blocker)
        assert started.wait(5)
        # both pending while the blocker holds the only slot; heavy's
        # arrived first
        f_heavy = sched.submit("heavy", lambda: "h")
        f_cheap = sched.submit("cheap", lambda: "c")
        for f in (b, f_heavy, f_cheap):
            f.result(timeout=30)
        order = [qid for qid, _ in sched.schedule_log]
        assert order == ["heavy", "cheap", "heavy"], order
    finally:
        sched.close()


def test_fifo_policy_preserves_arrival():
    sched = GlobalStageScheduler(slots=1, fair_share=False, seed=1)
    try:
        sched.register_query("heavy")
        sched.register_query("cheap")
        started = threading.Event()

        def blocker():
            started.set()
            time.sleep(0.08)

        b = sched.submit("heavy", blocker)
        assert started.wait(5)
        f_heavy = sched.submit("heavy", lambda: "h")
        f_cheap = sched.submit("cheap", lambda: "c")
        for f in (b, f_heavy, f_cheap):
            f.result(timeout=30)
        order = [qid for qid, _ in sched.schedule_log]
        assert order == ["heavy", "heavy", "cheap"], order
    finally:
        sched.close()


def test_scheduler_bounded_slots_and_stats():
    sched = GlobalStageScheduler(slots=2, fair_share=True, seed=0)
    try:
        sched.register_query("q")
        _run_all(sched, [("q", lambda: time.sleep(0.03))
                         for _ in range(8)])
        st = sched.stats()
        assert st["slots"] == 2
        assert sched.peak_in_flight <= 2
        assert st["pending_stages"] == 0
        assert st["policy"] == "fair_share"
    finally:
        sched.close()


def test_scheduler_selection_deterministic_given_seed():
    """Selection is a PURE FUNCTION of scheduler state (priority, pass,
    seeded registration-order tie-break, cost hint, arrival): the same
    backlog over the same state drains in the same order on independent
    scheduler instances. (Wall-clock pass values vary run to run — the
    determinism contract is the selection function, with byte-identical
    results guaranteed under any interleaving.)"""
    from datafusion_distributed_tpu.runtime.serving import _StageJob

    def drain(seed):
        sched = GlobalStageScheduler(slots=1, fair_share=True, seed=seed)
        sched.close()  # stop the workers; drive _pick_locked by hand
        state = {"qa": 0.30, "qb": 0.05, "qc": 0.05, "qd": 0.0}
        for i, (q, p) in enumerate(state.items()):
            sched._pass[q] = p
            sched._prio[q] = 0
            sched._weight[q] = 1.0
            sched._qseq[q] = i
        for seq, (q, hint) in enumerate([
            ("qa", 10), ("qb", 20), ("qc", 20), ("qd", 5),
            ("qb", 5), ("qc", 5), ("qa", 1),
        ]):
            sched._pending.append(_StageJob(q, None, seq, hint))
        order = []
        while sched._pending:
            order.append(sched._pick_locked().qid)
        return order

    o1 = drain(7)
    assert o1 == drain(7), "same seed, same state -> same schedule"
    # lowest-pass query first; the highest-pass query drains last
    assert o1[0] == "qd"
    assert o1[-2:] == ["qa", "qa"]


def test_stage_dag_cost_hints(tpch_ctx):
    from datafusion_distributed_tpu.planner.distributed import (
        build_stage_dag,
        stage_device_bytes,
    )

    df = tpch_ctx.sql(TPCH_Q3)
    plan = df.distributed_plan(4, config=df._seeded_host_config(4))
    dag = build_stage_dag(plan)
    assert dag is not None and len(dag.nodes) >= 2
    for node in dag.nodes.values():
        assert node.est_bytes == stage_device_bytes(node.exchange)
        assert node.est_bytes > 0


def test_serving_overlap_beats_serialized(tpch_ctx):
    """The tentpole's throughput claim in miniature: 4 closed-loop
    clients against the shared pool finish a fixed workload faster than
    the same workload serialized (max_concurrent_queries=1), because
    stages of DIFFERENT queries overlap across the cluster. A uniform
    injected execute delay stands in for device/DCN latency; both arms
    pay it identically per task."""
    workload = [TPCH_Q6, TPCH_Q1, TPCH_Q6, TPCH_Q1]

    def run(max_conc):
        chaos = _delay_cluster(workers=4, delay_s=0.15)
        with ServingSession(tpch_ctx, cluster=chaos, num_tasks=4,
                            max_concurrent_queries=max_conc) as srv:
            t0 = time.monotonic()
            handles = [srv.submit(sql) for sql in workload]
            for h in handles:
                h.result(timeout=600)
            return time.monotonic() - t0

    run(4)  # warm every compile cache before timing
    seq = run(1)
    conc = run(4)
    assert conc < seq, (
        f"concurrent serving ({conc:.2f}s) not faster than serialized "
        f"({seq:.2f}s)"
    )


# ---------------------------------------------------------------------------
# prepared statements on the serving path
# ---------------------------------------------------------------------------


def test_prepared_statement_binding_and_results(tpch_ctx):
    p = tpch_ctx.prepare(Q6_TEMPLATE)
    assert sorted(p.param_names) == ["d1", "d2", "hi", "lo", "qty"]
    params = {"d1": datetime.date(1994, 1, 1),
              "d2": datetime.date(1995, 1, 1),
              "lo": 0.05, "hi": 0.07, "qty": 24}
    got = p.execute(params)
    ref = tpch_ctx.sql(TPCH_Q6).collect()
    _assert_frames_identical(got.to_pandas(), ref.to_pandas(), "prep/q6")
    with pytest.raises(ValueError, match="missing parameters"):
        p.execute({"d1": datetime.date(1994, 1, 1)})
    with pytest.raises(TypeError, match="parameter type"):
        p.execute({**params, "qty": object()})
    # a datetime with a time-of-day must not silently truncate to a date
    with pytest.raises(TypeError, match="time-of-day"):
        p.execute({**params,
                   "d1": datetime.datetime(1994, 1, 1, 23, 59)})
    # a midnight datetime binds losslessly
    from datafusion_distributed_tpu.sql.context import _format_param
    assert _format_param(
        datetime.datetime(1994, 1, 1)
    ) == "date '1994-01-01'"
    # $ inside a string literal is not a placeholder
    p2 = tpch_ctx.prepare(
        "select count(*) as c from lineitem "
        "where l_returnflag <> '$x' and l_quantity < $q"
    )
    assert p2.param_names == ["q"]
    # ... nor inside -- / /* */ comments or "quoted identifiers"
    p3 = tpch_ctx.prepare(
        'select count(*) as c -- price in $USD\n'
        'from lineitem /* $block */ where l_quantity < $q'
    )
    assert p3.param_names == ["q"]


def test_prepared_serving_zero_new_compiles(tpch_ctx):
    """The recompile-budget gate extended to the serving path: after one
    warming submission, parameter variations served through the
    ServingSession (coordinated path, worker stage compiles included)
    perform ZERO new XLA traces."""
    p = tpch_ctx.prepare(Q6_TEMPLATE)
    variants = [
        {"d1": datetime.date(1994, 1, 1), "d2": datetime.date(1995, 1, 1),
         "lo": 0.05, "hi": 0.07, "qty": 24},
        {"d1": datetime.date(1995, 1, 1), "d2": datetime.date(1996, 1, 1),
         "lo": 0.03, "hi": 0.05, "qty": 35},
        {"d1": datetime.date(1993, 6, 1), "d2": datetime.date(1994, 6, 1),
         "lo": 0.02, "hi": 0.09, "qty": 11},
    ]
    with ServingSession(tpch_ctx, num_workers=4, num_tasks=4) as srv:
        # warm: the first binding compiles every stage program
        p.submit(srv, variants[0]).result(timeout=600)
        before = phys.trace_count()
        handles = [p.submit(srv, v) for v in variants[1:]]
        outs = [h.result(timeout=600) for h in handles]
        new_traces = phys.trace_count() - before
    assert new_traces == 0, (
        f"{new_traces} new traces serving literal-only variants"
    )
    # and the bindings actually produced distinct (correct) results
    refs = [
        tpch_ctx.sql(p.bind_sql(v)).collect_coordinated(
            coordinator=_coord(InMemoryCluster(4)), num_tasks=4
        )
        for v in variants[1:]
    ]
    for out, ref in zip(outs, refs):
        _assert_frames_identical(out.to_pandas(), ref.to_pandas(),
                                 "prep/serving")


# ---------------------------------------------------------------------------
# bookkeeping bounds (satellites)
# ---------------------------------------------------------------------------


def test_metrics_store_lru_never_evicts_running():
    from datafusion_distributed_tpu.runtime import metrics as m

    store = MetricsStore()
    store.begin_query("pinned")
    store.record_stage_span("pinned", 0, 0.0, 0.0, 1.0)
    for i in range(m._STAGE_SPAN_QUERY_CAP + 16):
        store.record_stage_span(f"q{i}", 0, 0.0, 0.0, 0.5)
    assert "pinned" in store.stage_spans, "running query evicted"
    assert len(store.stage_spans) <= m._STAGE_SPAN_QUERY_CAP + 1
    store.finish_query("pinned")
    for i in range(m._STAGE_SPAN_QUERY_CAP + 16):
        store.record_stage_span(f"r{i}", 0, 0.0, 0.0, 0.5)
    assert "pinned" not in store.stage_spans  # unpinned -> evictable
    assert len(store.stage_spans) <= m._STAGE_SPAN_QUERY_CAP


def test_chaos_query_scoped_schedules_replay_per_query():
    """query_scoped: two queries observe the IDENTICAL seeded fault
    sequence regardless of interleaving; sweep_query drops the state."""
    spec = FaultSpec(site="execute", kind="crash", rate=0.5)

    def kinds_for(plan, qid):
        out = []
        for task in range(6):
            got = plan.decide(
                "execute", "mem://w0", TaskKey(qid, 0, task)
            )
            out.append(got.kind if got else None)
        return out

    plan = FaultPlan(CHAOS_SEED, [spec], query_scoped=True)
    a = kinds_for(plan, "query-a")
    b = kinds_for(plan, "query-b")
    assert a == b, (a, b)
    assert plan._calls
    plan.sweep_query("query-a")
    assert all(ck[1] != "query-a" for ck in plan._calls)
    plan.sweep_query("query-b")
    assert not plan._calls
    # unscoped keeps the accumulated pre-serving semantics: the second
    # query's rolls CONTINUE the call count, so the sequences differ in
    # general (same seed, later nth values)
    legacy = FaultPlan(CHAOS_SEED, [spec])
    la = kinds_for(legacy, "query-a")
    lb = kinds_for(legacy, "query-b")
    assert la == a  # first query identical either way
    assert lb != la or legacy._calls  # counts accumulated plan-wide


def test_tablestore_ids_unique_under_concurrency():
    """uuid-based slice ids can never alias across in-flight queries —
    N threads staging into one store produce N distinct ids."""
    from datafusion_distributed_tpu.ops.table import Table
    from datafusion_distributed_tpu.runtime.codec import TableStore

    import jax.numpy as jnp

    store = TableStore()
    tbl = Table(("x",), (), jnp.zeros((), jnp.int32))
    ids: list = []
    lock = threading.Lock()

    def stage():
        got = [store.put(tbl) for _ in range(50)]
        with lock:
            ids.extend(got)

    threads = [threading.Thread(target=stage) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(ids) == len(set(ids)) == 400


def test_external_cancel_event_survives_execute_retry(tpch_ctx):
    """The serving tier presets a cancel REQUEST event on the per-query
    coordinator. A failed execute()'s internal teardown must NOT poison
    that event for a later attempt on the same coordinator (the
    overflow-retry loops re-enter execute()): after a fatal first
    attempt, a clean second attempt succeeds, and only an EXTERNAL set
    aborts it."""
    cancel_ev = threading.Event()
    cluster = wrap_cluster(InMemoryCluster(2), FaultPlan(CHAOS_SEED, [
        # exactly one injected crash, no retries: attempt 1 fails fatally
        FaultSpec(site="execute", kind="crash", rate=1.0, max_total=1),
    ]))
    coord = _coord(cluster, max_task_retries=0)
    coord.cancel_event = cancel_ev
    df = tpch_ctx.sql(TPCH_Q6)
    with pytest.raises(Exception) as ei:
        coord.execute(df.distributed_plan(
            2, config=df._seeded_host_config(2), coordinator=coord
        ))
    assert not isinstance(ei.value, TaskCancelledError)
    # attempt 2 on the SAME coordinator: the internal teardown signal
    # from attempt 1 must not linger
    out = coord.execute(df.distributed_plan(
        2, config=df._seeded_host_config(2), coordinator=coord
    ))
    assert int(out.num_rows) >= 0
    # an EXTERNAL cancel request does abort the next attempt
    cancel_ev.set()
    with pytest.raises(TaskCancelledError):
        coord.execute(df.distributed_plan(
            2, config=df._seeded_host_config(2), coordinator=coord
        ))


def test_coordinator_sweep_query_drops_per_query_state():
    cluster = InMemoryCluster(2)
    coord = _coord(cluster)
    key_a = TaskKey("qa", 0, 0)
    key_b = TaskKey("qb", 0, 0)
    coord.metrics[key_a] = {"elapsed_s": 1.0}
    coord.metrics[key_b] = {"elapsed_s": 2.0}
    coord.stream_metrics[("qa", 0)] = {"bytes_streamed": 1}
    coord.stream_metrics[("qb", 0)] = {"bytes_streamed": 2}
    coord.sweep_query("qa")
    assert key_a not in coord.metrics and key_b in coord.metrics
    assert ("qa", 0) not in coord.stream_metrics
    assert ("qb", 0) in coord.stream_metrics


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_observability_and_console_serving_surface(tpch_ctx):
    import io

    from datafusion_distributed_tpu.console import Console
    from datafusion_distributed_tpu.runtime.observability import (
        ObservabilityService,
    )

    with ServingSession(tpch_ctx, num_workers=2, num_tasks=2) as srv:
        srv.submit(TPCH_Q6).result(timeout=300)
        obs = ObservabilityService(srv.cluster, srv.cluster, serving=srv)
        st = obs.get_serving_stats()
        assert st["admitted_total"] == 1
        assert st["completed"][DONE] == 1
        assert st["active"] == 0 and st["queued"] == 0
        assert "scheduler" in st and st["scheduler"]["slots"] >= 1
        frame = Console(srv.cluster, srv.cluster, out=io.StringIO(),
                        serving=srv).render_frame()
        assert "serving" in frame
        assert "1 admitted" in frame
    # a session-free console renders no serving line
    cluster = InMemoryCluster(1)
    frame = Console(cluster, cluster, out=io.StringIO()).render_frame()
    assert "serving" not in frame
