"""Fused multiway hash-join stages + global-hash-table aggregation.

Gate for `SET distributed.multiway_join` / `SET distributed.global_hash_agg`
(planner/distributed._multiway_fusion_pass / _inject_global_agg,
plan/joins.MultiwayHashJoinExec):

- fusion-pass units: the broadcast same-stage link (case A), the
  identity-re-shuffle link (case B, deletes the interior exchanges),
  the no-fusion conditions, and the knob's default-off
- MultiwayHashJoinExec byte-identity vs the binary chain it fused
- TPC-H e2e byte identity fused-vs-unfused through the coordinator:
  q5/q9 under the default broadcast config (case A) and q21 co-shuffled
  (case B, `dftpu_exchanges_deleted` >= 2), under seeded chaos and
  membership churn; q7 and the chaos matrix ride the @slow lane
- global-hash-agg exactness vs the partial+final merge shape (integer
  aggregates: byte-exact, not approximately equal), plus the low-NDV
  negative (the gate must keep the merge shape there)
- coordinator bailout (runtime/coordinator._bailout_multiway): measured
  build rows over the captured table sizing swap the fused stage back to
  its rederived binary chain; padded (non-measured) capacities never bail
- zero new XLA traces when a fused query is resubmitted
- static-verifier arms: DFTPU011/012 (multiway step schema), DFTPU023
  (capacity), DFTPU034 (mixed co-shuffle widths)
"""

import os

import numpy as np
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.plan.exchanges import ShuffleExchangeExec
from datafusion_distributed_tpu.plan.joins import (
    HashJoinExec,
    MultiwayHashJoinExec,
    MultiwayJoinStep,
)
from datafusion_distributed_tpu.plan.physical import (
    DistributedTaskContext,
    ExecContext,
    MemoryScanExec,
    trace_count,
)
from datafusion_distributed_tpu.plan.verify import verify_physical_plan
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
from datafusion_distributed_tpu.runtime.telemetry import DEFAULT_REGISTRY

CHAOS_SEED = int(os.environ.get("DFTPU_CHAOS_SEED", "20260803"))
FAST = {"task_retry_backoff_s": 0.001}

_QDIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "queries", "tpch")


def _q(name: str) -> str:
    with open(os.path.join(_QDIR, f"{name}.sql")) as f:
        return f.read()


def _counter(name: str) -> float:
    fam = DEFAULT_REGISTRY.snapshot().get(name, {})
    return sum(v for _, v in fam.get("samples", []))


_TPCH_TABLES = None


@pytest.fixture(scope="module", autouse=True)
def _tpch_tables():
    from datafusion_distributed_tpu.data.tpchgen import gen_tpch

    global _TPCH_TABLES
    _TPCH_TABLES = gen_tpch(sf=0.002, seed=7)
    yield


def _mkctx(**dopts):
    """Fresh session over the shared sf=0.002 tables. Planner knobs are
    SESSION options: collect_coordinated_table plans from the session's
    distributed_snapshot, not from coordinator config_options."""
    from datafusion_distributed_tpu.sql.context import SessionContext

    ctx = SessionContext()
    ctx.config.distributed_options["bytes_per_task"] = 1  # force fan-out
    for k, v in dopts.items():
        ctx.config.distributed_options[k] = v
    for name, arrow in _TPCH_TABLES.items():
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


def _assert_frames_identical(got, base, label=""):
    assert list(got.columns) == list(base.columns), label
    for col in base.columns:
        np.testing.assert_array_equal(
            got[col].to_numpy(), base[col].to_numpy(),
            err_msg=f"{label}.{col} diverged under fusion",
        )


def _assert_no_leaks(cluster):
    for w in cluster.workers.values():
        assert not w.table_store.tables, (
            f"{w.url} leaked TableStore entries"
        )
        assert len(w.registry) == 0, f"{w.url} leaked registry entries"


def _mw_nodes(plan):
    return plan.collect(lambda n: isinstance(n, MultiwayHashJoinExec))


# ---------------------------------------------------------------------------
# fusion-pass units
# ---------------------------------------------------------------------------


def test_fusion_off_by_default():
    ctx = _mkctx()
    plan = ctx.sql(_q("q5")).distributed_plan(num_tasks=4)
    assert not _mw_nodes(plan), "multiway fusion fired without the knob"


def test_fusion_case_a_broadcast_chain():
    """Default broadcast config: q5's five joins chain directly (no probe
    exchanges) and fuse into ONE stage; nothing to delete."""
    ctx = _mkctx(multiway_join=True)
    f0 = _counter("dftpu_joins_fused")
    plan = ctx.sql(_q("q5")).distributed_plan(num_tasks=4)
    mws = _mw_nodes(plan)
    assert len(mws) == 1
    assert len(mws[0].steps) == 5
    assert mws[0].multiway_deleted_exchanges == 0
    assert mws[0].multiway_bailout_candidate
    assert _counter("dftpu_joins_fused") - f0 >= 5


def test_fusion_case_b_identity_shuffle_deletion():
    """Co-shuffled q21: the consecutive probe re-shuffles on l1.l_orderkey
    are identity re-partitions; fusing the inner/semi/anti chain deletes
    the two interior ones."""
    ctx = _mkctx(multiway_join=True, broadcast_joins=False,
                 broadcast_threshold_rows=0)
    d0 = _counter("dftpu_exchanges_deleted")
    plan = ctx.sql(_q("q21")).distributed_plan(num_tasks=4)
    mws = _mw_nodes(plan)
    assert len(mws) == 1
    mw = mws[0]
    assert len(mw.steps) == 3
    assert mw.multiway_deleted_exchanges == 2
    # the fused stage runs on the base shuffle's layout
    assert isinstance(mw.probe, ShuffleExchangeExec)
    assert _counter("dftpu_exchanges_deleted") - d0 >= 2


def test_fusion_stops_on_rekeying_shuffle():
    """Co-shuffled q5 re-hashes a DIFFERENT key at every step — deleting
    those shuffles would re-route rows, so no identity link forms."""
    ctx = _mkctx(multiway_join=True, broadcast_joins=False,
                 broadcast_threshold_rows=0)
    plan = ctx.sql(_q("q5")).distributed_plan(num_tasks=4)
    assert not _mw_nodes(plan), (
        "fused across a re-keying shuffle: that deletion is not an "
        "identity re-partition"
    )


# ---------------------------------------------------------------------------
# MultiwayHashJoinExec byte-identity vs its binary chain
# ---------------------------------------------------------------------------


def _exec_node(node, leaves):
    ctx = ExecContext(task=DistributedTaskContext(0, 1), inputs={})
    for leaf, table in leaves:
        ctx.inputs[leaf.node_id] = table
    return node.execute(ctx)


def _mk_mw_fixture(rng, n=1500, nb=96):
    pt = arrow_to_table(pa.table({
        "k1": rng.integers(0, nb, n), "k2": rng.integers(0, nb, n),
        "pv": np.arange(n),
    }))
    b1 = arrow_to_table(pa.table({
        "k1": rng.integers(0, nb, nb), "b1": np.arange(nb),
    }))
    b2 = arrow_to_table(pa.table({
        "k2": rng.integers(0, nb, nb), "b2": np.arange(nb),
    }))
    sp = MemoryScanExec([pt], pt.schema())
    s1 = MemoryScanExec([b1], b1.schema())
    s2 = MemoryScanExec([b2], b2.schema())
    j1 = HashJoinExec(sp, s1, ["k1"], ["k1"], "inner")
    j2 = HashJoinExec(j1, s2, ["k2"], ["k2"], "inner")
    mw = MultiwayHashJoinExec(sp, [s1, s2], [
        MultiwayJoinStep.from_join(j1), MultiwayJoinStep.from_join(j2),
    ])
    leaves = [(sp, pt), (s1, b1), (s2, b2)]
    return j2, mw, leaves


def _assert_tables_identical(got, base):
    g, b = got.to_pandas(), base.to_pandas()
    assert list(g.columns) == list(b.columns)
    assert len(g) == len(b)
    for col in b.columns:
        np.testing.assert_array_equal(g[col].to_numpy(),
                                      b[col].to_numpy(), err_msg=col)


def test_multiway_exec_reference_chain_byte_identical():
    rng = np.random.default_rng(3)
    chain, mw, leaves = _mk_mw_fixture(rng)
    _assert_tables_identical(_exec_node(mw, leaves),
                             _exec_node(chain, leaves))


# ---------------------------------------------------------------------------
# TPC-H e2e byte identity through the coordinator
# ---------------------------------------------------------------------------

#: query -> extra session options. q5/q9 fuse via the broadcast same-stage
#: link (case A); q21 co-shuffled fuses via identity-shuffle deletion
#: (case B: broadcast disabled so every join side arrives shuffled)
_COSHUFFLE = {"broadcast_joins": False, "broadcast_threshold_rows": 0}
_E2E = {"q5": {}, "q9": {}, "q21": _COSHUFFLE}
_E2E_SLOW = {"q7": {}}


def _fused_vs_unfused(qname, opts, cluster_fn=lambda: InMemoryCluster(4),
                      expect_deleted=0):
    sql = _q(qname)
    base, _ = _run(_mkctx(**opts), sql, InMemoryCluster(4))
    f0 = _counter("dftpu_joins_fused")
    d0 = _counter("dftpu_exchanges_deleted")
    got, coord = _run(_mkctx(multiway_join=True, **opts), sql,
                      cluster_fn())
    assert _counter("dftpu_joins_fused") > f0, f"{qname} never fused"
    assert _counter("dftpu_exchanges_deleted") - d0 >= expect_deleted
    _assert_frames_identical(got, base, qname)


@pytest.mark.parametrize("qname", sorted(_E2E))
def test_tpch_fused_byte_identity(qname):
    _fused_vs_unfused(
        qname, _E2E[qname],
        expect_deleted=2 if qname == "q21" else 0,
    )


def test_tpch_fused_byte_identity_under_chaos():
    cluster = InMemoryCluster(4)
    chaos = wrap_cluster(cluster, one_crash_per_stage(CHAOS_SEED))
    _fused_vs_unfused("q5", _E2E["q5"], cluster_fn=lambda: chaos)
    assert chaos.plan.fired, "chaos schedule never fired"
    _assert_no_leaks(cluster)


def test_tpch_fused_byte_identity_under_churn():
    cluster = DynamicCluster(4)
    victim = cluster.get_urls()[-1]
    chaos = wrap_cluster(cluster, FaultPlan(CHAOS_SEED, [], membership=[
        MembershipEvent("leave", victim, site="execute", nth_call=1),
    ]))
    _fused_vs_unfused("q9", _E2E["q9"], cluster_fn=lambda: chaos)
    assert victim not in cluster.get_urls()
    _assert_no_leaks(cluster)


@pytest.mark.slow
@pytest.mark.parametrize("qname", sorted(_E2E) + sorted(_E2E_SLOW))
def test_tpch_fused_byte_identity_chaos_matrix(qname):
    opts = {**_E2E, **_E2E_SLOW}[qname]
    cluster = InMemoryCluster(4)
    chaos = wrap_cluster(cluster, one_crash_per_stage(CHAOS_SEED))
    _fused_vs_unfused(qname, opts, cluster_fn=lambda: chaos,
                      expect_deleted=2 if qname == "q21" else 0)
    _assert_no_leaks(cluster)


def test_fused_resubmission_zero_new_traces():
    """Resubmitting an identical fused query through the same cluster
    performs ZERO new XLA compiles (the fused stage's fingerprint is
    stable, so every worker serves its compiled program from cache)."""
    ctx = _mkctx(multiway_join=True)
    sql = _q("q5")
    cluster = InMemoryCluster(4)
    base, _ = _run(ctx, sql, cluster)
    t0 = trace_count()
    again, _ = _run(ctx, sql, cluster)
    assert trace_count() == t0, (
        "resubmitting a fused query re-traced XLA programs"
    )
    _assert_frames_identical(again, base, "q5-resubmit")


# ---------------------------------------------------------------------------
# global-hash-table aggregation
# ---------------------------------------------------------------------------

#: near-unique composite key (reduction ~1.0 > the 0.2 pushdown floor) so
#: _inject_global_agg selects the single global table; integer aggregates
#: so the fused-vs-merge comparison is byte-exact
_GA_SQL = (
    "select l_orderkey, l_linenumber, count(*) as cnt, "
    "sum(l_quantity) as sq, min(l_partkey) as mn, max(l_suppkey) as mx "
    "from lineitem group by l_orderkey, l_linenumber"
)
_GA_KEYS = ["l_orderkey", "l_linenumber"]


def _sorted(df):
    return df.sort_values(_GA_KEYS).reset_index(drop=True)


def test_global_hash_agg_exact_vs_merge():
    base, _ = _run(_mkctx(), _GA_SQL, InMemoryCluster(4))
    g0 = _counter("dftpu_global_agg_selected")
    got, _ = _run(_mkctx(global_hash_agg=True), _GA_SQL, InMemoryCluster(4))
    assert _counter("dftpu_global_agg_selected") > g0, (
        "high-NDV aggregate never took the global-hash shape"
    )
    _assert_frames_identical(_sorted(got), _sorted(base), "global-agg")


def test_global_agg_not_selected_on_low_ndv():
    ctx = _mkctx(global_hash_agg=True)
    g0 = _counter("dftpu_global_agg_selected")
    _run(ctx, "select l_linenumber, count(*) c from lineitem "
              "group by l_linenumber", InMemoryCluster(4))
    assert _counter("dftpu_global_agg_selected") == g0, (
        "low-NDV aggregate must keep the partial+final merge shape"
    )


# ---------------------------------------------------------------------------
# coordinator bailout
# ---------------------------------------------------------------------------


def _shrunk_steps(steps, num_slots=8):
    return [
        MultiwayJoinStep(
            probe_keys=s.probe_keys, build_keys=s.build_keys,
            join_type=s.join_type, out_capacity=s.out_capacity,
            num_slots=num_slots, residual=s.residual,
            mark_name=s.mark_name, expansion_factor=s.expansion_factor,
            null_aware=s.null_aware,
        )
        for s in steps
    ]


def _coord():
    cluster = InMemoryCluster(2)
    return Coordinator(resolver=cluster, channels=cluster,
                       config_options=dict(FAST))


def test_bailout_swaps_fused_stage_back_to_chain():
    """Measured build rows above the captured per-step table sizing swap
    the fused node for its rederived binary chain, byte-identically."""
    rng = np.random.default_rng(6)
    chain, mw, leaves = _mk_mw_fixture(rng)
    # lie about the captured sizing: 8 slots against a 96-row build
    bad = MultiwayHashJoinExec(mw.probe, mw.builds,
                               _shrunk_steps(mw.steps))
    bad.multiway_bailout_candidate = True
    b0 = _counter("dftpu_multiway_bailouts")
    swapped = _coord()._bailout_multiway(bad, "qtest")
    assert isinstance(swapped, HashJoinExec)
    assert _counter("dftpu_multiway_bailouts") > b0
    _assert_tables_identical(_exec_node(swapped, leaves),
                             _exec_node(chain, leaves))


def test_bailout_ignores_padded_capacities():
    """Capacity paddings (non-MemoryScan builds) are the planner's own
    numbers, not measurements — they must never trigger a bail-out. This
    is the rule that keeps the peer/stream planes (whose rows never cross
    the coordinator) from spuriously unfusing every stage."""
    rng = np.random.default_rng(6)
    _, mw, _ = _mk_mw_fixture(rng)
    shuffled = MultiwayHashJoinExec(
        mw.probe,
        [ShuffleExchangeExec(mw.builds[0], ["k1"], 4, 1 << 14),
         ShuffleExchangeExec(mw.builds[1], ["k2"], 4, 1 << 14)],
        _shrunk_steps(mw.steps),
    )
    shuffled.multiway_bailout_candidate = True
    b0 = _counter("dftpu_multiway_bailouts")
    assert _coord()._bailout_multiway(shuffled, "qtest") is shuffled
    assert _counter("dftpu_multiway_bailouts") == b0


def test_bailout_skips_non_candidates():
    rng = np.random.default_rng(6)
    _, mw, _ = _mk_mw_fixture(rng)
    tight = MultiwayHashJoinExec(mw.probe, mw.builds,
                                 _shrunk_steps(mw.steps))
    # no multiway_bailout_candidate annotation -> hand-built node, hands off
    assert _coord()._bailout_multiway(tight, "qtest") is tight


# ---------------------------------------------------------------------------
# static-verifier arms
# ---------------------------------------------------------------------------


def test_verifier_accepts_planner_fused_node():
    ctx = _mkctx(multiway_join=True)
    plan = ctx.sql(_q("q5")).distributed_plan(num_tasks=4)
    r = verify_physical_plan(plan)
    assert r.ok, [str(i) for i in r.issues]


def test_verifier_multiway_unknown_key_DFTPU011():
    rng = np.random.default_rng(8)
    _, mw, _ = _mk_mw_fixture(rng)
    bad = MultiwayHashJoinExec(mw.probe, mw.builds, [
        MultiwayJoinStep(
            probe_keys=("no_such",), build_keys=("k1",),
            join_type="inner", out_capacity=64, num_slots=64,
        ),
        mw.steps[1],
    ])
    r = verify_physical_plan(bad)
    assert "DFTPU011" in r.codes() and not r.ok


def test_verifier_multiway_key_class_mismatch_DFTPU012():
    rng = np.random.default_rng(8)
    _, mw, _ = _mk_mw_fixture(rng)
    ft = arrow_to_table(pa.table({"k1": np.linspace(0.0, 1.0, 8)}))
    bad = MultiwayHashJoinExec(
        mw.probe, [MemoryScanExec([ft], ft.schema()), mw.builds[1]], [
            MultiwayJoinStep(
                probe_keys=("k1",), build_keys=("k1",),
                join_type="inner", out_capacity=64, num_slots=64,
            ),
            mw.steps[1],
        ],
    )
    r = verify_physical_plan(bad)
    assert "DFTPU012" in r.codes() and not r.ok


def test_verifier_multiway_slots_below_build_bound_DFTPU023():
    rng = np.random.default_rng(8)
    _, mw, _ = _mk_mw_fixture(rng)
    small = MultiwayHashJoinExec(mw.probe, mw.builds,
                                 _shrunk_steps(mw.steps, num_slots=8))
    r = verify_physical_plan(small)
    assert "DFTPU023" in r.codes()
    assert r.ok  # warning only: the claim loop retries, never corrupts


def test_verifier_multiway_mixed_shuffle_widths_DFTPU034():
    rng = np.random.default_rng(8)
    _, mw, _ = _mk_mw_fixture(rng)
    bad = MultiwayHashJoinExec(
        mw.probe,
        [ShuffleExchangeExec(mw.builds[0], ["k1"], 4, 64),
         ShuffleExchangeExec(mw.builds[1], ["k2"], 8, 64)],
        list(mw.steps),
    )
    r = verify_physical_plan(bad)
    assert "DFTPU034" in r.codes() and not r.ok
