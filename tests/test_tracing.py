"""Distributed tracing gate (runtime/tracing.py).

Acceptance contract (ISSUE 9): hierarchical spans
query -> stage -> task -> attempt with worker-side spans joined via
cross-wire context propagation (in-process AND gRPC transports);
retry/heal/cancel events under seeded chaos + membership churn; byte
counters matching table `nbytes`; tracing=off adds ZERO spans and ZERO
new XLA traces (span ids must never enter a compile-cache key); a
distributed TPC-H run's span tree covers >= 95% of measured query wall
with no unattributed gap over 5%; serving-path traces isolated per
query id; bounded memory (per-query ring buffer + cross-query LRU
pinning running queries); DFTPU109 keeps span/clock calls out of
jax-traced code.

Determinism: assertions are on span ORDERING and tree shape over the
monotonic clock — never wall-clock comparisons.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.plan import physical as phys
from datafusion_distributed_tpu.plan.physical import (
    HashAggregateExec,
    MemoryScanExec,
)
from datafusion_distributed_tpu.planner.distributed import (
    DistributedConfig,
    build_stage_dag,
    distribute_plan,
)
from datafusion_distributed_tpu.runtime.chaos import (
    FaultPlan,
    MembershipEvent,
    one_crash_per_stage,
    wrap_cluster,
)
from datafusion_distributed_tpu.runtime.coordinator import (
    Coordinator,
    DynamicCluster,
    InMemoryCluster,
)
from datafusion_distributed_tpu.runtime.errors import TaskCancelledError
from datafusion_distributed_tpu.runtime import tracing
from datafusion_distributed_tpu.runtime.tracing import (
    DEFAULT_TRACE_STORE,
    NULL_TRACER,
    TraceStore,
    layer_report,
    table_nbytes,
    render_profile,
    stage_data_rates,
    to_chrome_trace,
    trace_coverage,
)
from datafusion_distributed_tpu.runtime.worker import Worker

CHAOS_SEED = int(os.environ.get("DFTPU_CHAOS_SEED", "20260803"))
FAST = {"task_retry_backoff_s": 0.001, "tracing": "on"}

# Inlined TPC-H texts (the reference checkout's testdata/ is absent in
# this container): q3 for the span-tree shape, q5 for the coverage
# acceptance — the bushy plans whose sibling stages overlap.
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


TPCH_Q1 = """
select l_returnflag, l_linestatus,
       sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc, count(*) as count_order
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

# one group a live order: with too few slots the first attempt overflows
HIGH_NDV = "select l_orderkey, count(*) as n from lineitem group by l_orderkey"


@pytest.fixture(scope="module")
def tpch_ctx():
    from datafusion_distributed_tpu.data.tpchgen import gen_tpch
    from datafusion_distributed_tpu.sql.context import SessionContext

    ctx = SessionContext()
    ctx.config.distributed_options["bytes_per_task"] = 1  # force fan-out
    ctx.config.distributed_options["broadcast_joins"] = False
    for name, arrow in gen_tpch(sf=0.002, seed=7).items():
        ctx.register_arrow(name, arrow)
    return ctx


def _plan(n=2048, num_tasks=4):
    rng = np.random.default_rng(3)
    t = arrow_to_table(pa.table({
        "k": rng.integers(0, 16, n),
        "v": rng.normal(size=n),
    }))
    scan = MemoryScanExec([t], t.schema())
    agg = HashAggregateExec(
        "single", ["k"], [AggSpec("sum", "v", "sv")], scan, 32
    )
    return distribute_plan(agg, DistributedConfig(num_tasks=num_tasks))


def _coord(cluster, **opts):
    return Coordinator(resolver=cluster, channels=cluster,
                       config_options={**FAST, **opts})


def _run_tpch(ctx, sql, cluster, **opts):
    df = ctx.sql(sql)
    coord = _coord(cluster, **opts)
    out = df._strip_quals(
        df.collect_coordinated_table(coordinator=coord, num_tasks=4)
    ).to_pandas()
    return out, coord


def _assert_monotonic_tree(trace):
    """Every span well-ordered on the monotonic clock and (loosely)
    nested inside its parent; parents resolve within the trace."""
    spans = trace.span_list()
    by_id = {s.span_id: s for s in spans}
    root = trace.root_span()
    assert root is not None
    for s in spans:
        assert s.t1 >= s.t0, (s.name, s.t0, s.t1)
        if s.span_id == root.span_id:
            continue
        parent = by_id.get(s.parent_id)
        assert parent is not None, f"{s.name} has dangling parent"
        # ordering on ONE monotonic clock: a child never starts before
        # its parent (small epsilon for cross-thread recording). A
        # worker's phases (live or spliced: `worker_decode`,
        # `worker_execute`, `worker_output`) hang under the parent the
        # task envelope carried and may legitimately END after it:
        # peer-plane producers execute LAZILY at first consumer
        # pull, long after the dispatch that shipped them — the trace
        # records that truthfully instead of faking nesting.
        assert s.t0 >= parent.t0 - 0.05, (s.name, parent.name)
        if not (s.attrs.get("remote") or s.name.startswith("worker_")):
            assert s.t1 <= parent.t1 + 0.05, (s.name, parent.name)


# ---------------------------------------------------------------------------
# store bounds: per-query ring + cross-query LRU with running pinned
# ---------------------------------------------------------------------------


def test_trace_store_ring_buffer_and_lru():
    store = TraceStore(query_cap=2, span_cap=8)
    tr1 = store.begin("q1", "on")
    for i in range(20):
        with tr1.span(f"s{i}", "task"):
            pass
    trace1 = store.get("q1")
    assert len(trace1.span_list()) == 8  # ring bound
    assert trace1.dropped == 12          # evictions surfaced
    # LRU across queries: q1 still RUNNING is pinned through pressure
    store.begin("q2", "on")
    store.begin("q3", "on")
    store.finish("q2")
    store.finish("q3")
    assert store.get("q1") is not None, "running trace must never evict"
    store.finish("q1")
    store.begin("q4", "on")
    store.finish("q4")
    assert len([q for q in ("q1", "q2", "q3", "q4")
                if store.get(q) is not None]) <= 2


def test_sampled_mode_deterministic():
    store = TraceStore()
    assert store.begin("abc", "sampled", sample_rate=1.0).active
    assert store.begin("abc2", "sampled", sample_rate=0.0) is NULL_TRACER
    assert store.begin("abc3", "off") is NULL_TRACER


# ---------------------------------------------------------------------------
# span-tree shape: distributed TPC-H q3, worker spans joined cross-wire
# ---------------------------------------------------------------------------


def test_q3_span_tree_shape(tpch_ctx):
    cluster = InMemoryCluster(4)
    _out, coord = _run_tpch(tpch_ctx, TPCH_Q3, cluster)
    trace = coord.last_query_trace()
    assert trace is not None and trace.finished
    spans = trace.span_list()
    by_id = {s.span_id: s for s in spans}
    kinds = {s.kind for s in spans}
    assert {"query", "stage", "task", "attempt", "dispatch",
            "execute"} <= kinds, sorted(kinds)
    # every task span parents under its stage span
    task_spans = [s for s in spans if s.kind == "task"]
    assert task_spans
    for s in task_spans:
        parent = by_id[s.parent_id]
        assert parent.kind == "stage"
        assert parent.attrs.get("stage") == s.attrs.get("stage")
    # worker-side spans joined via the propagated trace context: live
    # ones, since an in-process worker finds the running trace (PR 38)
    worker_side = [s for s in spans
                   if s.name in ("worker_decode", "worker_execute")]
    assert {s.name for s in worker_side} == {"worker_decode",
                                             "worker_execute"}
    for s in worker_side:
        assert s.parent_id in by_id, "wire parent did not resolve"
        assert not s.attrs.get("remote"), "an in-process span was spliced"
    # planner cost hints rode onto stage spans
    staged = [s for s in spans
              if s.kind == "stage" and s.attrs.get("stage", -1) >= 0]
    assert any("est_bytes" in s.attrs for s in staged)
    _assert_monotonic_tree(trace)
    # Chrome export is valid JSON with events for every span
    chrome = to_chrome_trace(trace)
    parsed = json.loads(json.dumps(chrome))
    assert len([e for e in parsed["traceEvents"] if e["ph"] == "X"]) == (
        len(spans)
    )


# ---------------------------------------------------------------------------
# acceptance: q5 coverage >= 95%, per-stage bytes/sec, explain fold
# ---------------------------------------------------------------------------


def test_q5_coverage_and_data_rates(tpch_ctx):
    # the acceptance flow: the knob set through SQL, not constructor args
    tpch_ctx.sql("set distributed.tracing = 'on'")
    try:
        cluster = InMemoryCluster(4)
        df = tpch_ctx.sql(TPCH_Q5)
        coord = Coordinator(
            resolver=cluster, channels=cluster,
            config_options=tpch_ctx.config.distributed_snapshot(),
        )
        df.collect_coordinated_table(coordinator=coord, num_tasks=4)
    finally:
        tpch_ctx.config.distributed_options.pop("tracing", None)
    trace = coord.last_query_trace()
    assert trace is not None
    cov, max_gap = trace_coverage(trace)
    assert cov >= 0.95, f"span tree covers only {cov:.1%} of query wall"
    assert max_gap <= 0.05, f"unattributed gap of {max_gap:.1%}"
    # worker-side spans joined through the propagated context
    assert any(s.name == "worker_execute" for s in trace.span_list())
    # per-stage exchange bytes/sec measured
    rates = stage_data_rates(trace)
    assert rates, "no per-stage data-plane attribution"
    assert any(slot.get("bytes_per_s") for slot in rates.values())
    profile = render_profile(trace)
    assert "per-stage data plane" in profile
    assert "GB/s" in profile
    # chrome export valid
    chrome = json.loads(json.dumps(to_chrome_trace(trace)))
    assert chrome["traceEvents"]
    # the profile folds into explain_analyze for the executed plan
    from datafusion_distributed_tpu.runtime.metrics import explain_analyze

    plan = df.distributed_plan(4, config=df._seeded_host_config(4),
                               coordinator=coord)
    text = explain_analyze(plan, coord.stage_metrics)
    assert "-- trace profile" in text
    # ctx.last_trace(): the Perfetto surface from the session
    assert tpch_ctx.last_trace() is not None


# ---------------------------------------------------------------------------
# byte attribution: encode-span counters == staged table nbytes
# ---------------------------------------------------------------------------


class _ByteCountingWorker(Worker):
    """Records the true nbytes of every table slice staged into it."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.staged_bytes: list = []

    def set_plan(self, key, plan_obj, task_count, **kw):
        from datafusion_distributed_tpu.runtime.codec import (
            collect_table_ids,
        )

        self.staged_bytes.append(sum(
            table_nbytes(self.table_store.get(tid))
            for tid in collect_table_ids(plan_obj)
        ))
        return super().set_plan(key, plan_obj, task_count, **kw)


def test_encode_bytes_match_table_nbytes():
    cluster = InMemoryCluster(2)
    cluster.workers = {
        url: _ByteCountingWorker(url) for url in cluster.get_urls()
    }
    for w in cluster.workers.values():
        w.peer_channels = cluster
    coord = _coord(cluster)
    coord.execute(_plan())
    trace = coord.last_query_trace()
    encode_spans = [s for s in trace.span_list()
                    if s.kind == "codec" and not s.attrs.get("remote")]
    assert encode_spans
    span_total = sum(int(s.attrs.get("bytes", 0)) for s in encode_spans)
    staged_total = sum(
        b for w in cluster.workers.values() for b in w.staged_bytes
    )
    # identical by construction: both sides sum column data+validity
    # nbytes of the staged slices (codec framing adds nothing in-process)
    assert span_total == staged_total, (span_total, staged_total)
    assert span_total > 0


# ---------------------------------------------------------------------------
# fault-path events: retry (chaos), heal (membership churn), cancel
# ---------------------------------------------------------------------------


def _event_names(trace):
    return [name for _t, name, _a, _p in trace.event_list()]


def test_retry_events_under_seeded_chaos():
    cluster = InMemoryCluster(3)
    chaos = wrap_cluster(cluster, one_crash_per_stage(CHAOS_SEED))
    coord = _coord(chaos)
    coord.execute(_plan())
    trace = coord.last_query_trace()
    names = _event_names(trace)
    assert "task_retry" in names, names
    retries = [a for _t, n, a, _p in trace.event_list()
               if n == "task_retry"]
    assert all("error" in a and "stage" in a for a in retries)


def test_heal_and_membership_events_under_churn():
    cluster = DynamicCluster(3)
    victim = cluster.get_urls()[0]
    chaos = wrap_cluster(cluster, FaultPlan(CHAOS_SEED, [], membership=[
        MembershipEvent("leave", victim, site="execute", nth_call=0),
    ]))
    coord = _coord(chaos)
    coord.execute(_plan())
    trace = coord.last_query_trace()
    names = _event_names(trace)
    assert "membership_change" in names, names
    assert "peer_heal" in names or "task_retry" in names, names
    if coord.faults.get("peer_producers_reshipped"):
        assert "peer_heal" in names, names


def test_cancel_events():
    cluster = InMemoryCluster(2)
    cancel = threading.Event()
    cancel.set()
    coord = Coordinator(resolver=cluster, channels=cluster,
                        config_options=dict(FAST), cancel_event=cancel)
    with pytest.raises(TaskCancelledError):
        coord.execute(_plan())
    trace = coord.last_query_trace()
    assert trace is not None
    assert "task_cancelled" in _event_names(trace)


# ---------------------------------------------------------------------------
# cross-wire propagation over the gRPC transport
# ---------------------------------------------------------------------------


def test_grpc_cross_wire_spans():
    from datafusion_distributed_tpu.runtime.grpc_worker import (
        start_localhost_cluster,
    )

    cluster = start_localhost_cluster(2)
    try:
        coord = _coord(cluster)
        coord.execute(_plan(n=1024, num_tasks=2))
        trace = coord.last_query_trace()
        spans = trace.span_list()
        by_id = {s.span_id: s for s in spans}
        remote = [s for s in spans if s.attrs.get("remote")]
        assert remote, "worker spans did not cross the gRPC wire"
        for s in remote:
            assert str(s.attrs.get("worker", "")).startswith("grpc://")
            assert s.parent_id in by_id, (
                "gRPC worker span not joined to propagated parent"
            )
        # wire-level dispatch bytes recorded next to staged nbytes
        assert any(s.attrs.get("wire_bytes") for s in spans
                   if s.kind == "dispatch")
    finally:
        cluster.shutdown()
    # a context that crossed a wire is never looked up in the registry of
    # running traces (PR 38), a localhost server in the coordinator's own
    # process too: its phases are spliced dicts and carry no children
    tasks = [s for s in spans if s.name == "worker_execute"]
    assert tasks and {s.span_id for s in tasks} <= {
        s.span_id for s in remote}
    for task in tasks:
        assert task.kind == "worker"
        assert "new_traces" in task.attrs and "running" not in task.attrs
        assert not any(s.parent_id == task.span_id for s in spans)
    (row,) = [r for r in layer_report() if trace.query_id in r["traces"]]
    assert row["counters"]["tasks"] == len(tasks)
    assert row["counters"]["syncs"] == 0


# ---------------------------------------------------------------------------
# off-mode: zero spans, zero new XLA traces (recompile-gate extension)
# ---------------------------------------------------------------------------


def test_tracing_off_zero_spans_and_zero_compiles():
    cluster = InMemoryCluster(2)
    dplan = _plan()
    coord_off = Coordinator(resolver=cluster, channels=cluster,
                            config_options={"task_retry_backoff_s": 0.001})
    coord_off.execute(dplan)  # warm: compiles happen here
    qid_off = coord_off.last_query_id
    assert DEFAULT_TRACE_STORE.get(qid_off) is None, (
        "tracing off must record zero spans"
    )
    n0 = phys.trace_count()
    coord_off.execute(dplan)
    assert phys.trace_count() == n0, "off-mode resubmission recompiled"
    # tracing ON over the same warm plan: trace context must not enter
    # any compile-cache key — still ZERO new XLA traces
    coord_on = _coord(cluster)
    n1 = phys.trace_count()
    coord_on.execute(dplan)
    assert phys.trace_count() == n1, (
        "enabling tracing caused new XLA traces — span ids leaked into "
        "a compile-cache key"
    )
    assert coord_on.last_query_trace() is not None


def test_tracing_off_zero_spans_direct_and_mesh_tiers(tpch_ctx):
    """No profiler session and no SET: the direct and mesh tiers hold the
    null tracer too, leave nothing in the store, and turning tracing on
    afterwards compiles nothing (no span or request id keys a program)."""
    from datafusion_distributed_tpu.runtime.mesh_executor import make_mesh

    mesh = make_mesh(2)
    tpch_ctx.sql(TPCH_Q1).to_pandas()  # warm: compiles happen here
    tpch_ctx.sql(TPCH_Q1).collect_distributed(mesh=mesh)
    DEFAULT_TRACE_STORE.clear()
    n0 = phys.trace_count()
    tpch_ctx.sql(TPCH_Q1).to_pandas()
    tpch_ctx.sql(TPCH_Q1).collect()
    tpch_ctx.sql(TPCH_Q1).collect_distributed(mesh=mesh)
    assert DEFAULT_TRACE_STORE.finished_traces() == []
    assert layer_report() == []
    assert tracing.current() is NULL_TRACER
    df = tpch_ctx.sql(TPCH_Q1)
    assert tracing.request_of(df.collect_table()) is None
    assert df.request_id is None, "a request id was minted with tracing off"
    tpch_ctx.config.distributed_options["tracing"] = "on"
    try:
        tpch_ctx.sql(TPCH_Q1).to_pandas()
        tpch_ctx.sql(TPCH_Q1).collect_distributed(mesh=mesh)
    finally:
        tpch_ctx.config.distributed_options.pop("tracing", None)
    assert phys.trace_count() == n0, "tracing on recompiled a program"
    assert len(layer_report()) == 2


# ---------------------------------------------------------------------------
# serving path: traces isolated per query id
# ---------------------------------------------------------------------------


def test_serving_traces_isolated_per_query(tpch_ctx):
    from datafusion_distributed_tpu.runtime.serving import ServingSession

    tpch_ctx.config.distributed_options["tracing"] = "on"
    try:
        with ServingSession(tpch_ctx, num_workers=2) as srv:
            h1 = srv.submit(TPCH_Q3)
            h2 = srv.submit(
                "select count(*) as n from lineitem"
            )
            h1.result(timeout=600)
            h2.result(timeout=600)
    finally:
        tpch_ctx.config.distributed_options.pop("tracing", None)
    assert h1.trace_query_id and h2.trace_query_id
    assert h1.trace_query_id != h2.trace_query_id
    t1, t2 = h1.query_trace(), h2.query_trace()
    assert t1 is not None and t2 is not None
    assert t1.query_id != t2.query_id
    # per-query isolation: the traces share no span objects
    spans2 = {id(s) for s in t2.span_list()}
    assert not any(id(s) in spans2 for s in t1.span_list())
    assert h1.trace() is not None and h2.trace() is not None
    # the handle is named on its execute's root; the admission wait is
    # the request's `queued` span, a trace of its own under the request
    root = t1.root_span()
    assert root.attrs["serving_query_id"] == h1.query_id
    assert root.attrs["request"] == h1.request_id
    assert "admission_wait_s" not in root.attrs
    rows = {r["request"]: r for r in layer_report()}
    for h in (h1, h2):
        row = rows[h.request_id]
        assert {"submit", "queued", "query"} <= set(row["self_s"])
        assert row["total_s"]["queued"] == pytest.approx(h.queue_wait_s())
    assert h1.trace_profile()


# ---------------------------------------------------------------------------
# observability satellites
# ---------------------------------------------------------------------------


def test_get_task_progress_degrades_per_worker():
    from datafusion_distributed_tpu.runtime.observability import (
        ObservabilityService,
    )
    from datafusion_distributed_tpu.runtime.worker import TaskKey

    class _DeadWorker:
        def task_progress(self, key):
            raise ConnectionError("worker went away")

    class _OkWorker:
        def task_progress(self, key):
            return {"rows_out": 7}

    class _Cluster:
        def get_urls(self):
            return ["mem://dead", "mem://ok"]

        def get_worker(self, url):
            return _DeadWorker() if "dead" in url else _OkWorker()

    obs = ObservabilityService(_Cluster(), _Cluster())
    key = TaskKey("q", 0, 0)
    out = obs.get_task_progress([key])
    assert out[key]["rows_out"] == 7
    assert out[key]["worker"] == "mem://ok"


def test_trace_summary_and_console_panel():
    from datafusion_distributed_tpu.console import Console
    from datafusion_distributed_tpu.runtime.observability import (
        ObservabilityService,
    )

    cluster = InMemoryCluster(2)
    coord = _coord(cluster)
    coord.execute(_plan())
    obs = ObservabilityService(cluster, cluster)
    summary = obs.get_trace_summary()
    assert summary["traces"] >= 1
    assert summary["spans"] > 0
    assert summary["spans_by_kind"].get("stage")
    frame = Console(cluster, cluster).render_frame()
    assert "tracing" in frame


# ---------------------------------------------------------------------------
# ISSUE 27: the profiler session is the switch; one span, two sinks; the
# direct and mesh tiers trace too; one report a request
# ---------------------------------------------------------------------------


def _run_tier(ctx, tier: str, sql: str):
    """One request on a tier, fetched to the host. -> (result frame,
    the request's identifier, its overflow retries)."""
    if tier == "direct":
        df = ctx.sql(sql)
        table = df.collect_table()
        return table.to_pandas(), df.request_id, df.last_retry_count
    if tier == "mesh":
        from datafusion_distributed_tpu.runtime.mesh_executor import make_mesh

        df = ctx.sql(sql)
        out = df.collect_distributed(mesh=make_mesh(2))
        return out.to_pandas(), df.request_id, df.last_retry_count
    from datafusion_distributed_tpu.runtime.serving import ServingSession

    with ServingSession(ctx, num_workers=2, num_tasks=2) as srv:
        h = srv.submit(sql)
        out = h.result(timeout=600)
        return out.to_pandas(), h.request_id, h.retry_count


def _request_spans(request_id: str) -> dict:
    """kind -> [spans] over every finished trace of one request."""
    out: dict = {}
    for trace in DEFAULT_TRACE_STORE.finished_traces():
        if trace.request != request_id:
            continue
        root = trace.root_span()
        assert root.attrs["request"] == request_id
        for s in trace.span_list():
            out.setdefault(s.kind, []).append(s)
    return out


def _host_events(trace_dir) -> list:
    """(name, start_ns, end_ns) of every `dftpu.*` event in the host planes
    of the profile a `jax.profiler` session left under ``trace_dir``."""
    import glob

    import jax

    (path,) = glob.glob(
        os.path.join(str(trace_dir), "plugins", "profile", "*",
                     "*.xplane.pb")
    )
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PROFILE_PREFIX):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns))
    return events


@pytest.mark.parametrize("tier", ["direct", "coordinator", "mesh"])
def test_profiler_session_switches_tracing_on_and_off(tier, tpch_ctx,
                                                      tmp_path):
    """No SET: a recording `jax.profiler` session traces the request on
    every tier, its spans are `dftpu.*` events in the profile's host plane
    inside the session's window, and the session's end turns it off."""
    import jax

    assert "tracing" not in tpch_ctx.config.distributed_options
    _run_tier(tpch_ctx, tier, TPCH_Q1)  # warm, untraced
    DEFAULT_TRACE_STORE.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert jax.profiler.TraceAnnotation.is_enabled()
        with jax.profiler.TraceAnnotation("test.window"):
            _frame, request_id, _ = _run_tier(tpch_ctx, tier, TPCH_Q1)
    finally:
        jax.profiler.stop_trace()
    spans = _request_spans(request_id)
    want = {
        "direct": {"sql", "parse", "plan", "query", "attempt", "prepare",
                   "execute", "fetch"},
        "coordinator": {"submit", "sql", "parse", "plan", "queued", "query",
                        "schedule", "stage", "task", "attempt", "dispatch",
                        "prepare", "execute", "fetch"},
        "mesh": {"sql", "parse", "plan", "query", "attempt",
                 "mesh.stack_inputs", "mesh.execute", "fetch"},
    }[tier]
    assert want <= set(spans), sorted(set(spans))
    (row,) = [r for r in layer_report() if r["request"] == request_id]
    assert row["counters"]["transfers"] > 0
    assert row["counters"]["round_trips"] == 1  # the fetch joined its row
    # the second sink: the same spans in the profiler's own trace
    events = _host_events(tmp_path)
    names = {name for name, _, _ in events}
    live = {"direct": {"sql", "parse", "plan", "query", "attempt",
                       "prepare", "execute", "fetch"},
            "coordinator": {"submit", "query", "schedule", "task",
                            "dispatch", "worker_execute", "execute",
                            "fetch"},
            "mesh": {"query", "mesh.stack_inputs", "mesh.execute",
                     "fetch"}}[tier]
    assert {tracing.PROFILE_PREFIX + n for n in live} <= names, sorted(names)
    # after-the-fact spans (the stage spans, `queued`) stay in the store
    assert tracing.PROFILE_PREFIX + "queued" not in names
    # the session's end is the switch's: nothing is traced any more
    DEFAULT_TRACE_STORE.clear()
    _run_tier(tpch_ctx, tier, TPCH_Q1)
    assert DEFAULT_TRACE_STORE.finished_traces() == []


def test_direct_q1_span_tree_under_one_request(tpch_ctx):
    tpch_ctx.sql(TPCH_Q1).to_pandas()  # warm
    DEFAULT_TRACE_STORE.clear()
    tpch_ctx.config.distributed_options["tracing"] = "on"
    try:
        df = tpch_ctx.sql(TPCH_Q1)
        table = df.collect_table()
        frame = table.to_pandas()
    finally:
        tpch_ctx.config.distributed_options.pop("tracing", None)
    assert len(frame) == 4
    traces = [t for t in DEFAULT_TRACE_STORE.finished_traces()
              if t.request == df.request_id]

    def tree(trace):
        spans = trace.span_list()
        kids: dict = {}
        for s in spans:
            kids.setdefault(s.parent_id, []).append(s)

        def walk(s):
            below = sorted(kids.get(s.span_id, []), key=lambda k: k.t0)
            return (s.kind, [walk(k) for k in below])

        return walk(trace.root_span())

    assert [tree(t) for t in traces] == [
        ("sql", [("parse", []), ("plan", [])]),
        ("query", [("attempt", [
            ("prepare", [("h2d", [])]),
            ("execute", [("launch", []), ("sync", [])]),
        ])]),
        ("fetch", []),
    ]
    for t in traces:
        _assert_monotonic_tree(t)
    (row,) = [r for r in layer_report() if r["request"] == df.request_id]
    assert len(row["traces"]) == 3
    assert row["counters"]["retries"] == 0
    assert row["counters"]["new_traces"] == 0
    # ten output columns, data and validity each where nullable, and the
    # row count: every one a device-to-host pull
    fetch = traces[2].root_span()
    assert fetch.attrs["rows"] == 4
    assert fetch.attrs["transfers"] == 1 + sum(
        1 + (c.validity is not None) for c in table.columns
    )
    # and one round trip: the buffers are copied together, whole, and cut
    # to the four rows on the host
    assert fetch.attrs["round_trips"] == 1
    assert row["counters"]["round_trips"] == 1
    assert row["counters"]["transfers"] == fetch.attrs["transfers"]
    assert "layers (self time by span kind)" in render_profile(traces[1])
    assert (f"transfers {fetch.attrs['transfers']}  round_trips 1  retries 0"
            in render_profile(traces[2]))
    # a DataFrame that is never collected pins nothing in the store
    tpch_ctx.config.distributed_options["tracing"] = "on"
    try:
        tpch_ctx.sql(TPCH_Q1)
    finally:
        tpch_ctx.config.distributed_options.pop("tracing", None)
    assert DEFAULT_TRACE_STORE.summary()["running"] == 0


@pytest.mark.parametrize("tier,query,masked,direct,dense", [
    # one filter, under the aggregate's projection; two dictionary keys,
    # a domain of 6: dense reductions
    ("direct", TPCH_Q1, 1, 1, 1),
    # four stacked filters under the global aggregate: no group-by, one slot
    ("direct", TPCH_Q6, 4, 0, 1),
    # three filters, each under a join: they compact; integer group keys,
    # the claim loop and the scatters
    ("direct", TPCH_Q3, 0, 0, 0),
    # the SPMD program's partial aggregate takes the mask; the partial and
    # the final aggregate both address their groups directly and reduce
    # densely
    ("mesh", TPCH_Q1, 1, 2, 2),
    ("mesh", TPCH_Q6, 4, 0, 2),
], ids=["q1", "q6", "q3", "mesh-q1", "mesh-q6"])
def test_execute_span_carries_the_trace_counters(tpch_ctx, tier, query,
                                                 masked, direct, dense):
    """`masked_filters` (the filters that handed an aggregate their mask),
    `direct_groupings` (the aggregates that addressed their groups by
    dictionary codes) and `dense_aggregates` (the aggregates, grouped or
    global, that reduced by dense passes and not by scatters) on the
    `execute` span (`mesh.execute` on the mesh tier). Counted when the
    program is traced and kept with the cached executable, so a
    program-cache hit reports them too."""
    from datafusion_distributed_tpu.runtime.mesh_executor import make_mesh

    kind = {"direct": "execute", "mesh": "mesh.execute"}[tier]
    tpch_ctx.config.distributed_options["tracing"] = "on"
    try:
        requests = []
        for _ in range(2):
            df = tpch_ctx.sql(query)
            if tier == "mesh":
                df.collect_distributed_table(mesh=make_mesh(2))
            else:
                df.collect_table()
            requests.append(df.request_id)
    finally:
        tpch_ctx.config.distributed_options.pop("tracing", None)
    for request_id in requests:
        spans = _request_spans(request_id)
        (execute,) = spans[kind]
        assert execute.attrs["masked_filters"] == masked
        assert execute.attrs["direct_groupings"] == direct
        assert execute.attrs["dense_aggregates"] == dense
        (row,) = [r for r in layer_report() if r["request"] == request_id]
        assert row["counters"]["masked_filters"] == masked
        assert row["counters"]["direct_groupings"] == direct
        assert row["counters"]["dense_aggregates"] == dense
    spans = _request_spans(requests[1])
    (cached,) = spans["prepare"] if tier == "direct" else spans[kind]
    assert cached.attrs["cache"] == "hit"
    (execute,) = spans[kind]
    assert execute.attrs["new_traces"] == 0


def test_untraced_cache_hit_after_a_traced_collect_leaves_no_trace(
        tpch_ctx):
    """`SET distributed.result_cache = on`: the traced collect that fills
    the cache tags a Table of its own with the request, never the object
    the cache holds, so a later hit with tracing off hands back an
    untagged Table whose fetch opens no trace and joins no old request;
    and with tracing off no request identifier is minted at all."""
    opts = tpch_ctx.config.distributed_options
    sql = "select count(*) as n from nation"
    opts["result_cache"] = True
    try:
        opts["tracing"] = "on"
        try:
            df = tpch_ctx.sql(sql)
            out = df.collect_coordinated_table()
            assert len(out.to_pandas()) == 1
        finally:
            opts.pop("tracing", None)
        assert tracing.request_of(out) == df.request_id is not None
        (row,) = [r for r in layer_report()
                  if r["request"] == df.request_id]
        transfers = row["counters"]["transfers"]
        assert transfers > 0
        assert row["counters"]["round_trips"] == 1
        hits0 = tpch_ctx.result_cache().stats()["hits"]
        DEFAULT_TRACE_STORE.clear()
        df2 = tpch_ctx.sql(sql)
        hit = df2.collect_coordinated_table()
        assert tpch_ctx.result_cache().stats()["hits"] == hits0 + 1
        assert hit is not out and tracing.request_of(hit) is None
        assert len(hit.to_pandas()) == 1
        assert len(df2.collect_coordinated()) == 1
        assert df2.request_id is None
        assert DEFAULT_TRACE_STORE.finished_traces() == []
        assert layer_report() == []
    finally:
        opts.pop("result_cache", None)
        tpch_ctx._result_cache = None


@pytest.mark.parametrize("tier,slot_factor", [
    ("direct", 0.3), ("coordinator", 0.07), ("mesh", 0.05),
])
def test_forced_overflow_is_two_attempts_and_one_retry(tier, slot_factor,
                                                       tpch_ctx):
    planner = tpch_ctx.config.planner
    saved = planner.agg_slot_factor
    planner.agg_slot_factor = slot_factor
    tpch_ctx.config.distributed_options["tracing"] = "on"
    DEFAULT_TRACE_STORE.clear()
    try:
        frame, request_id, retries = _run_tier(tpch_ctx, tier, HIGH_NDV)
    finally:
        planner.agg_slot_factor = saved
        tpch_ctx.config.distributed_options.pop("tracing", None)
    assert retries == 1
    assert len(frame) == len(set(frame["l_orderkey"]))
    (row,) = [r for r in layer_report() if r["request"] == request_id]
    assert row["counters"]["retries"] == 1
    spans = _request_spans(request_id)
    if tier == "coordinator":
        # an attempt is one `Coordinator.execute`, a trace of its own
        attempts = sorted(
            (s.attrs["attempt"], s.attrs.get("error"),
             s.attrs.get("retries")) for s in spans["query"]
        )
    else:
        (root,) = spans["query"]
        assert root.attrs["retries"] == 1
        attempts = sorted(
            (s.attrs["attempt"], s.attrs.get("error"), None)
            for s in spans["attempt"]
        )
        attempts[1] = attempts[1][:2] + (1,)
    assert [a[0] for a in attempts] == [0, 1]
    assert attempts[0][1] is not None and attempts[1][1] is None
    assert attempts[1][2] == 1


def test_d2h_and_h2d_bytes_are_table_nbytes_of_what_crossed():
    from datafusion_distributed_tpu.ops.table import host_view

    rng = np.random.default_rng(5)
    device = arrow_to_table(pa.table({
        "k": rng.integers(0, 8, 512), "v": rng.normal(size=512),
    }))
    store = TraceStore()
    with tracing.trace_call("query", {"tracing": "on"}, "r1",
                            store=store) as call:
        host = host_view(device)
        assert host_view(host) is host  # already on the host: no span
        scan = MemoryScanExec([host], host.schema())
        agg = HashAggregateExec(
            "single", ["k"], [AggSpec("sum", "v", "sv")], scan, 32
        )
        phys.execute_plan(agg)
    spans = call.tracer.trace.span_list()
    (d2h,) = [s for s in spans if s.kind == "d2h"]
    assert d2h.attrs["bytes"] == table_nbytes(device) > 0
    assert d2h.attrs["rows"] == 512
    assert d2h.attrs["capacity"] == device.capacity
    (h2d,) = [s for s in spans if s.kind == "h2d"]
    assert h2d.attrs["bytes"] == table_nbytes(host) == table_nbytes(device)
    (row,) = layer_report(store)
    assert row["counters"]["bytes"] == {
        "d2h": table_nbytes(device), "h2d": table_nbytes(host),
    }


def test_register_arrow_spans_say_where_a_registration_went():
    """Under `SET distributed.tracing` a table's registration is a request
    of its own in the report: the `register` span, an `encode` a string
    column (host dictionary encoding) and the `h2d` of the padded columns,
    each with its counters. With tracing off, and for a wire decode
    (`arrow_to_table` with no tracer, under an open trace), nothing."""
    from datafusion_distributed_tpu.sql.context import SessionContext

    rng = np.random.default_rng(9)
    words = np.array(["ash", "birch", "cedar", None], dtype=object)
    arrow = pa.table({
        "k": np.arange(100), "v": rng.normal(size=100),
        "s": pa.array(words[rng.integers(0, 4, 100)], pa.string()),
        "u": pa.array([f"row {i}" for i in range(100)], pa.string()),
    })
    ctx = SessionContext()
    DEFAULT_TRACE_STORE.clear()
    ctx.register_arrow("untraced", arrow)
    assert DEFAULT_TRACE_STORE.finished_traces() == []
    assert layer_report() == []
    ctx.sql("SET distributed.tracing = 'on'")
    DEFAULT_TRACE_STORE.clear()
    ctx.register_arrow("traced", arrow)
    (trace,) = DEFAULT_TRACE_STORE.finished_traces()
    spans = trace.span_list()
    by_id = {s.span_id: s for s in spans}
    root = trace.root_span()
    assert (root.name, root.kind) == ("register", "register")
    assert (root.attrs["table"], root.attrs["rows"],
            root.attrs["capacity"]) == ("traced", 100, 128)
    encodes = {s.attrs["column"]: s for s in spans if s.kind == "encode"}
    assert sorted(encodes) == ["s", "u"]
    assert (encodes["s"].attrs["rows"], encodes["s"].attrs["distinct"]) == (
        100, 3)
    assert (encodes["u"].attrs["rows"], encodes["u"].attrs["distinct"]) == (
        100, 100)
    assert encodes["u"].attrs["bytes"] == arrow.column("u").nbytes
    (h2d,) = [s for s in spans if s.kind == "h2d"]
    table = ctx.catalog.tables["traced"]
    # one validity array went up, ``s``'s (the only column with a NULL),
    # and the session says the same of the table (PR 37)
    assert h2d.attrs == {"bytes": table_nbytes(table), "masks": 1,
                         "rows": 100, "capacity": 128}
    assert ctx.table_masks("traced") == 1
    assert table_nbytes(table) == 128 * (4 * 4 + 1)
    for s in [h2d, *encodes.values()]:
        assert by_id[s.parent_id] is root
    _assert_monotonic_tree(trace)
    (row,) = layer_report()
    assert row["request"] == root.attrs["request"]
    assert set(row["self_s"]) == {"register", "encode", "h2d"}
    assert all(v >= 0 for v in row["self_s"].values())
    # the columns are encoded side by side, on threads: each lies inside the
    # registration, and the `h2d` begins when the last of them has ended
    for s in encodes.values():
        assert root.t0 <= s.t0 <= s.t1 <= h2d.t0 <= h2d.t1 <= root.t1
    assert row["counters"]["bytes"] == {
        "encode": arrow.column("s").nbytes + arrow.column("u").nbytes,
        "h2d": table_nbytes(table)}
    assert row["counters"]["masks"] == 1
    # the data the spans watched is the data a query reads
    got = ctx.sql("select count(*) as n, count(s) as s from traced"
                  ).to_pandas()
    assert (int(got["n"][0]), int(got["s"][0])) == (
        100, 100 - arrow.column("s").null_count)
    # a decode of the wire under an open trace adds no span of these kinds
    store = TraceStore()
    with tracing.trace_call("query", {"tracing": "on"}, "r1",
                            store=store) as call:
        arrow_to_table(arrow)
    assert [s.kind for s in call.tracer.trace.span_list()] == ["query"]


def test_exchange_spans_split_d2h_and_regroup(tpch_ctx):
    """Inside an exchange the host's work is named: the pull of the
    producers' outputs (`d2h`) and the regroup, children of the
    `exchange` span, with the bytes that crossed."""
    cluster = InMemoryCluster(2)
    _out, coord = _run_tpch(tpch_ctx, TPCH_Q1, cluster,
                            pipelined_shuffle=False, data_plane="unary")
    trace = coord.last_query_trace()
    spans = trace.span_list()
    by_id = {s.span_id: s for s in spans}

    def ancestors(s):
        while s.parent_id in by_id:
            s = by_id[s.parent_id]
            yield s.kind

    d2h = [s for s in spans if s.kind == "d2h"]
    regroup = [s for s in spans if s.kind == "regroup"]
    assert d2h and regroup
    for s in d2h + regroup:
        assert "exchange" in set(ancestors(s)), s.name
        assert s.attrs["bytes"] > 0
    h2d = [s for s in spans if s.kind == "h2d" and s.attrs["bytes"]]
    assert h2d, "no consumer scan handed host-staged bytes to a program"
    for s in h2d:
        assert "prepare" in set(ancestors(s))
    _assert_monotonic_tree(trace)


def test_layer_report_self_times_sum_to_the_wall():
    """Where no two tasks overlap, the layers add up: the self times of a
    request's spans, summed over kinds, are its roots' wall."""
    cluster = InMemoryCluster(1)
    store = TraceStore()
    coord = Coordinator(
        resolver=cluster, channels=cluster, trace_store=store,
        config_options={**FAST, "stage_parallelism": 1},
    )
    coord.execute(_plan(num_tasks=1))
    (row,) = layer_report(store)
    assert row["wall_s"] > 0
    assert sum(row["self_s"].values()) == pytest.approx(
        row["wall_s"], rel=1e-3
    )
    assert row["total_s"]["worker_execute"] > 0
    assert {"query", "schedule", "stage", "task", "execute"} <= set(
        row["self_s"]
    )


def test_scopes_name_the_kernels_and_change_metadata_only(tpch_ctx,
                                                          monkeypatch):
    """The lowered q1 and q3 programs carry the operator scopes in
    `op_name`; without them the optimized HLO differs in metadata only
    and the results are byte-equal."""
    import contextlib
    import re

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # jax leaves metadata out of the persistent cache's key, so a cached
    # executable would answer for both variants: compile afresh
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def programs(sql):
        plan = tpch_ctx.sql(sql).physical_plan()
        prog = phys._prepare_program(
            plan, phys.DistributedTaskContext(), None, False, None, None,
            NULL_TRACER,
        )
        lowered = prog.fn.lower(prog.inputs, prog.params)
        out = jax.tree_util.tree_leaves(
            prog.fn(prog.inputs, prog.params)[0]
        )
        return (lowered.as_text(debug_info=True),
                lowered.compile().as_text(),
                [np.asarray(x).tobytes() for x in out])

    def strip(hlo):
        """The module without its metadata: every op's `metadata={...}`
        and the tables of source locations the module opens with."""
        head, _, body = hlo.partition("\n\n")
        body = body[re.search(r"^(%|ENTRY)", body, re.M).start():]
        return head + re.sub(r", metadata=\{[^}]*\}", "", body)

    scoped = {q: programs(sql) for q, sql in
              (("q1", TPCH_Q1), ("q3", TPCH_Q3))}
    # q1 groups by two dictionary-coded columns: ids by arithmetic, no claim
    for name in ("agg.direct", "agg.reduce.sum", "sort.permutation",
                 "table.gather", "HashAggregateExec."):
        assert name in scoped["q1"][0], name
    assert "agg.claim" not in scoped["q1"][0]
    for name in ("agg.claim", "agg.reduce.sum", "join.build", "join.probe",
                 "join.expand", "sort.permutation", "HashJoinExec."):
        assert name in scoped["q3"][0], name
    # a node is named by its class and pre-order position, never its id
    assert re.search(r"SortExec\.0/", scoped["q1"][0])
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        for q, sql, scope in (("q1", TPCH_Q1, "agg.direct"),
                              ("q3", TPCH_Q3, "agg.claim")):
            lowered, optimized, result = programs(sql)
            assert scope not in lowered
            assert scope in scoped[q][1]
            assert scope not in optimized
            assert strip(optimized) == strip(scoped[q][1])
            assert result == scoped[q][2]
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# lint: DFTPU109 keeps spans/clocks out of jax-traced code
# ---------------------------------------------------------------------------


def test_dftpu109_flags_spans_in_traced_code(tmp_path):
    bad = tmp_path / "bad_kernel.py"
    bad.write_text(
        "import time\n"
        "from jax import jit\n"
        "def kernel(x):\n"
        "    t0 = time.monotonic()\n"
        "    with tracer.span('k', 'execute'):\n"
        "        y = x + 1\n"
        "    return y, t0\n"
        "f = jit(kernel)\n"
    )
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "check_tracer_safety.py"),
         "--json", str(bad)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    report = json.loads(proc.stdout)
    rules = {v["rule"] for v in report["violations"]}
    assert "DFTPU109" in rules, report
    # the profiler annotation a live span doubles as is host-side too;
    # `jax.named_scope` is the one instrumentation that belongs in a trace
    bad.write_text(
        "import jax\n"
        "from jax import jit\n"
        "def kernel(x):\n"
        "    with jax.profiler.TraceAnnotation('dftpu.k'):\n"
        "        y = x + 1\n"
        "    with jax.named_scope('agg.claim'):\n"
        "        y = y * 2\n"
        "    return y\n"
        "f = jit(kernel)\n"
    )
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "check_tracer_safety.py"),
         "--json", str(bad)],
        capture_output=True, text=True,
    )
    found = json.loads(proc.stdout)["violations"]
    assert [(v["rule"], v["line"]) for v in found] == [("DFTPU109", 4)], found
    # the package itself must stay clean under the new rule
    proc2 = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "check_tracer_safety.py")],
        capture_output=True, text=True,
    )
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr


# ---------------------------------------------------------------------------
# ISSUE 38: a worker's phases are live on whatever thread they run on; a
# span a launch, a device sync and an input wait; `syncs` and `tasks`
# ---------------------------------------------------------------------------


def _below(trace) -> dict:
    """span id -> its direct children, oldest first."""
    kids: dict = {}
    for s in sorted(trace.span_list(), key=lambda s: s.t0):
        kids.setdefault(s.parent_id, []).append(s)
    return kids


def _assert_worker_executes_hold_their_work(trace) -> list:
    """Every ``worker_execute`` span of ``trace`` holds its program's
    lookup, ``prepare`` and ``execute``, and ``execute`` its ``launch``
    and its ONE wait for the device: the flags, the metric values and
    with them the row count in one pull. -> the ``worker_execute``
    spans."""
    kids = _below(trace)
    tasks = [s for s in trace.span_list() if s.name == "worker_execute"]
    for task in tasks:
        assert task.kind == "worker" and not task.attrs.get("remote")
        assert task.attrs["running"] >= 0
        below = {k.name: k for k in kids.get(task.span_id, ())}
        assert {"program_lookup", "prepare", "execute"} <= set(below), (
            task.attrs, sorted(below))
        assert below["program_lookup"].kind == "prepare"
        assert below["program_lookup"].attrs["cache"] in ("hit", "miss")
        inside = kids.get(below["execute"].span_id, ())
        assert {"launch", "sync"} <= {k.name for k in inside}
        (pull,) = [k for k in inside if k.name == "sync"]
        assert (pull.kind, pull.attrs["what"], pull.attrs["syncs"]) == (
            "sync", "flags", 1)
        # the flag vector and at least the root's ``output_rows`` rode it
        assert pull.attrs["values"] >= 2
        # and the task reads the device for nothing else (ISSUE 39: the
        # metric values were a read each, the row count one more)
        assert not [k for k in kids[task.span_id] if k.name == "sync"]
    return tasks


def test_served_stage_tasks_record_live_spans(tpch_ctx):
    """q1 through a `ServingSession` at four tasks a stage: every stage
    task runs on a pull's thread, where no tracer is open, and still
    holds its children; a final-stage task's ``h2d`` is its wait for the
    producer stage, and says so."""
    from datafusion_distributed_tpu.runtime.serving import ServingSession

    tpch_ctx.config.distributed_options["tracing"] = "on"
    try:
        with ServingSession(tpch_ctx, num_workers=4, num_tasks=4) as srv:
            srv.submit(TPCH_Q1).result(timeout=600)  # warm
            h = srv.submit(TPCH_Q1)
            h.result(timeout=600)
    finally:
        tpch_ctx.config.distributed_options.pop("tracing", None)
    trace = h.query_trace()
    _assert_monotonic_tree(trace)
    tasks = _assert_worker_executes_hold_their_work(trace)
    assert len(tasks) > 2, "q1 did not fan out"
    kids = _below(trace)
    by_id = {s.span_id: s for s in trace.span_list()}
    self_s = {s.span_id: t for s, t in tracing.self_times(trace)}
    # the kinds self time can split: the transport's spans are `rpc`
    for s in trace.span_list():
        if s.name in ("pull", "execute_rpc", "ship"):
            assert s.kind == "rpc", s.name
    assert {s.name for s in trace.span_list()} >= {"pull", "execute_rpc"}
    # a consumer blocked on its producers: under `h2d`, which keeps the
    # hand-over as its own time
    waits = [s for s in trace.span_list() if s.name == "input_wait"]
    assert waits
    for wait in waits:
        assert wait.kind == "wait"
        h2d = by_id[wait.parent_id]
        assert h2d.name == "h2d"
        assert self_s[h2d.span_id] < wait.duration
    # a producer's output on its way to the consumers is spanned on the
    # thread that pulled it first
    outputs = [s for s in trace.span_list() if s.name == "worker_output"]
    assert outputs and all(s.kind == "exchange" for s in outputs)
    assert any(k.name == "d2h" for s in outputs
               for k in kids.get(s.span_id, ()))
    # the report: the blocking reads and the tasks of the request
    (row,) = [r for r in layer_report() if r["request"] == h.request_id]
    syncs = [s for s in trace.span_list() if s.name == "sync"]
    assert row["counters"]["syncs"] == sum(s.attrs["syncs"] for s in syncs)
    # one a task: a stage output's row count comes with `host_view`'s pull
    assert row["counters"]["syncs"] == len(tasks) == len(syncs)
    # 1 + the task's metric values (``output_rows`` a node): q1's four
    # producers hold five nodes, its four consumers three, the root two
    assert sorted(s.attrs["values"] for s in syncs) == (
        [3] + [4] * 4 + [6] * 4)
    assert row["counters"]["tasks"] == len(tasks)
    assert {"worker", "launch", "sync", "wait", "rpc"} <= set(row["self_s"])
    # what is left to `worker_execute` itself is little of it
    own = sum(self_s[t.span_id] for t in tasks)
    assert own <= 0.25 * row["total_s"]["worker_execute"]


def test_profile_holds_the_workers_spans(tpch_ctx, tmp_path):
    """One span, two sinks, on the workers' threads too: the profile of a
    served request holds a `dftpu.worker_execute` event a span of the
    store, and the launches, syncs and input waits."""
    import jax
    from datafusion_distributed_tpu.runtime.serving import ServingSession

    with ServingSession(tpch_ctx, num_workers=4, num_tasks=4) as srv:
        srv.submit(TPCH_Q1).result(timeout=600)  # warm, untraced
        DEFAULT_TRACE_STORE.clear()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            h = srv.submit(TPCH_Q1)
            h.result(timeout=600)
        finally:
            jax.profiler.stop_trace()
    stored: dict = {}
    for s in h.query_trace().span_list():
        stored[s.name] = stored.get(s.name, 0) + 1
    events: dict = {}
    for name, _, _ in _host_events(tmp_path):
        events[name] = events.get(name, 0) + 1
    for name in ("worker_execute", "launch", "sync", "input_wait",
                 "program_lookup", "worker_output"):
        assert stored[name] > 0
        assert events[tracing.PROFILE_PREFIX + name] == stored[name], name


def test_a_coordinator_with_its_own_store_joins_its_workers_spans():
    cluster = InMemoryCluster(2)
    own = TraceStore()
    coord = Coordinator(resolver=cluster, channels=cluster, trace_store=own,
                        config_options=dict(FAST))
    coord.execute(_plan(num_tasks=2))  # cold: the stage programs compile
    cold = own.get(coord.last_query_id)
    _assert_worker_executes_hold_their_work(cold)
    # the creator of a stage-shared program passes its first-call gate
    gates = [s for s in cold.span_list() if s.name == "gate_wait"]
    by_id = {s.span_id: s for s in cold.span_list()}
    assert gates and all(
        (s.kind, by_id[s.parent_id].name) == ("wait", "execute")
        for s in gates)
    coord.execute(_plan(num_tasks=2))
    trace = own.get(coord.last_query_id)
    assert _assert_worker_executes_hold_their_work(trace)
    assert DEFAULT_TRACE_STORE.get(coord.last_query_id) is None
    # the registry holds running traces only
    assert tracing._spans.running_tracer(coord.last_query_id) is NULL_TRACER


def test_a_task_on_the_deadline_thread_is_live():
    """`task_timeout_s` runs a task on `call_with_deadline`'s thread,
    where no tracer is open: the phase finds the running trace."""
    cluster = InMemoryCluster(2)
    coord = _coord(cluster, task_timeout_s=120.0, dispatch_timeout_s=120.0)
    coord.execute(_plan(num_tasks=2))
    trace = coord.last_query_trace()
    assert _assert_worker_executes_hold_their_work(trace)
    decodes = [s for s in trace.span_list() if s.name == "worker_decode"]
    assert decodes and not any(s.attrs.get("remote") for s in decodes)
    _assert_monotonic_tree(trace)


def test_tracing_off_looks_nothing_up(monkeypatch):
    lookups = []
    found = tracing._spans.running_tracer
    monkeypatch.setattr(
        tracing._spans, "running_tracer",
        lambda query_id: lookups.append(query_id) or found(query_id))
    cluster = InMemoryCluster(2)
    off = Coordinator(resolver=cluster, channels=cluster,
                      config_options={"task_retry_backoff_s": 0.001,
                                      "task_timeout_s": 120.0})
    off.execute(_plan(num_tasks=2))
    assert lookups == []
    assert DEFAULT_TRACE_STORE.get(off.last_query_id) is None
    assert off.last_query_id not in tracing._spans._RUNNING
    assert tracing._tasks_running == [0]
    on = _coord(cluster, task_timeout_s=120.0)
    on.execute(_plan(num_tasks=2))
    assert set(lookups) == {on.last_query_id}
    # the registry holds running traces only, the counter tasks in flight
    assert on.last_query_id not in tracing._spans._RUNNING
    assert tracing._tasks_running == [0]


@pytest.mark.parametrize("tier", ["direct", "coordinator", "mesh"])
def test_every_tier_splits_its_execute_into_launch_and_sync(tier, tpch_ctx):
    """The jitted call up to its return and the wait for the flag vector
    are two spans inside `execute` / `mesh.execute` on every tier, and a
    request's `syncs` and `tasks` say how many blocking reads and worker
    tasks it made: 1 and 0 for a direct q1."""
    _run_tier(tpch_ctx, tier, TPCH_Q1)  # warm
    tpch_ctx.config.distributed_options["tracing"] = "on"
    try:
        _frame, request_id, _ = _run_tier(tpch_ctx, tier, TPCH_Q1)
    finally:
        tpch_ctx.config.distributed_options.pop("tracing", None)
    spans = _request_spans(request_id)
    executes = spans["mesh.execute" if tier == "mesh" else "execute"]
    by_parent: dict = {}
    for s in spans["launch"] + spans["sync"]:
        by_parent.setdefault(s.parent_id, []).append(s)
    for execute in executes:
        launch, sync = sorted(by_parent[execute.span_id],
                              key=lambda s: s.t0)
        assert (launch.name, launch.kind) == ("launch", "launch")
        assert (sync.name, sync.kind) == ("sync", "sync")
        assert (sync.attrs["what"], sync.attrs["syncs"]) == ("flags", 1)
        assert execute.t0 <= launch.t0 <= launch.t1 <= sync.t0
        assert sync.t1 <= execute.t1
    (row,) = [r for r in layer_report() if r["request"] == request_id]
    assert row["total_s"]["launch"] > 0 and "sync" in row["self_s"]
    tasks = len(spans.get("worker", ()))
    assert row["counters"]["tasks"] == tasks
    assert (tasks > 0) == (tier == "coordinator")
    if tier == "coordinator":
        assert row["counters"]["syncs"] == tasks
        # 1 + the task's metric values (one ``output_rows`` a node)
        assert all(s.attrs["values"] >= 3 for s in spans["sync"])
    else:
        assert row["counters"]["syncs"] == 1
        assert [s.attrs["values"] for s in spans["sync"]] == [1]


def test_worker_phases_from_many_threads_keep_the_registry_and_the_count():
    """More threads than cores, a short switch interval: each begins a
    trace in a store of its own, runs worker phases that find it through
    the registry from a second thread, and finishes it. No phase joins
    another query's trace, the in-flight count comes back to zero and the
    registry holds nothing that finished."""
    from datafusion_distributed_tpu.runtime.tracing import worker_phase

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    errors: list = []
    tracers: dict = {}

    def one(i: int) -> None:
        try:
            store = TraceStore()
            for n in range(20):
                qid = f"stress-{i}-{n}"
                tracer = store.begin(qid, "on")
                root = tracer.open_root("query", "query")
                tctx = {"q": qid, "parent": root.span_id}

                def task():
                    assert tracing.current() is NULL_TRACER
                    with worker_phase(tctx, "worker_execute", "worker", [],
                                      count_task=True) as phase:
                        assert tracing.current() is tracer
                        with tracing.current().span("sync", "sync", syncs=1):
                            pass
                        phase.set(rows=n)

                worker = threading.Thread(target=task)
                worker.start()
                worker.join(timeout=60)
                assert not worker.is_alive()
                tracer.end_span(root)
                store.finish(qid)
                tracers[qid] = tracer  # alive: only `finish` empties
                names = sorted(s.name for s in store.get(qid).span_list())
                assert names == ["query", "sync", "worker_execute"], names
                (row,) = tracing._layer_rows([store.get(qid)])
                assert (row["counters"]["tasks"],
                        row["counters"]["syncs"]) == (1, 1)
        except BaseException as e:  # re-raised in the test's thread
            errors.append(e)

    try:
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(4 * (os.cpu_count() or 2))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    if errors:
        raise errors[0]
    assert tracing._tasks_running == [0]
    assert not any(q in tracing._spans._RUNNING for q in tracers)
