"""TPC-H join chains through the coordinator tier.

Binary hash-join chains split into stages at their exchanges, run on
in-process workers:

- q5/q9 under the default broadcast config and q21 co-shuffled (broadcast
  off, so every join side arrives through a hash shuffle) against the
  pandas oracle (tests/tpch_oracle.py)
- byte identity with the fault-free run under seeded chaos (one crash per
  stage) and membership churn (a worker leaves mid-query), with no
  TableStore or registry entry left on any worker; q7 and the chaos matrix
  ride the @slow lane
- zero new XLA traces when the same query is resubmitted
"""

import os
import re

import numpy as np
import pytest

from datafusion_distributed_tpu.plan.physical import trace_count
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

from tpch_oracle import ORACLES, compare_results, load_pandas

CHAOS_SEED = int(os.environ.get("DFTPU_CHAOS_SEED", "20260803"))
FAST = {"task_retry_backoff_s": 0.001}

_QDIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "queries", "tpch")


def _q(name: str) -> str:
    with open(os.path.join(_QDIR, f"{name}.sql")) as f:
        return f.read()


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


def _run(ctx, sql, cluster):
    df = ctx.sql(sql)
    coord = Coordinator(resolver=cluster, channels=cluster,
                        config_options=FAST)
    return df._strip_quals(
        df.collect_coordinated_table(coordinator=coord, num_tasks=4)
    ).to_pandas()


def _assert_frames_identical(got, base, label=""):
    assert list(got.columns) == list(base.columns), label
    for col in base.columns:
        np.testing.assert_array_equal(
            got[col].to_numpy(), base[col].to_numpy(),
            err_msg=f"{label}.{col} diverged from the fault-free run",
        )


def _assert_no_leaks(cluster):
    for w in cluster.workers.values():
        assert not w.table_store.tables, (
            f"{w.url} leaked TableStore entries"
        )
        assert len(w.registry) == 0, f"{w.url} leaked registry entries"


#: q5/q9 join through broadcast builds; q21 co-shuffled (broadcast
#: disabled so every join side arrives shuffled)
_COSHUFFLE = {"broadcast_joins": False, "broadcast_threshold_rows": 0}
_E2E = {"q5": {}, "q9": {}, "q21": _COSHUFFLE}
_E2E_SLOW = {"q7": {}}


def _faulted_vs_clean(qname, opts, cluster_fn):
    sql = _q(qname)
    base = _run(_mkctx(**opts), sql, InMemoryCluster(4))
    got = _run(_mkctx(**opts), sql, cluster_fn())
    _assert_frames_identical(got, base, qname)


@pytest.mark.parametrize("qname", sorted(_E2E))
def test_tpch_join_chain_vs_oracle(qname):
    sql = _q(qname)
    cluster = InMemoryCluster(4)
    got = _run(_mkctx(**_E2E[qname]), sql, cluster)
    exp = ORACLES[qname](load_pandas(_TPCH_TABLES))
    limit = re.search(r"\blimit\s+(\d+)\s*;?\s*$", sql, re.IGNORECASE)
    if limit:
        exp = exp.head(int(limit.group(1)))
    compare_results(got, exp)
    _assert_no_leaks(cluster)


def test_tpch_join_chain_under_chaos():
    cluster = InMemoryCluster(4)
    chaos = wrap_cluster(cluster, one_crash_per_stage(CHAOS_SEED))
    _faulted_vs_clean("q5", _E2E["q5"], cluster_fn=lambda: chaos)
    assert chaos.plan.fired, "chaos schedule never fired"
    _assert_no_leaks(cluster)


def test_tpch_join_chain_under_churn():
    cluster = DynamicCluster(4)
    victim = cluster.get_urls()[-1]
    chaos = wrap_cluster(cluster, FaultPlan(CHAOS_SEED, [], membership=[
        MembershipEvent("leave", victim, site="execute", nth_call=1),
    ]))
    _faulted_vs_clean("q9", _E2E["q9"], cluster_fn=lambda: chaos)
    assert victim not in cluster.get_urls()
    _assert_no_leaks(cluster)


@pytest.mark.slow
@pytest.mark.parametrize("qname", sorted(_E2E) + sorted(_E2E_SLOW))
def test_tpch_join_chain_chaos_matrix(qname):
    opts = {**_E2E, **_E2E_SLOW}[qname]
    cluster = InMemoryCluster(4)
    chaos = wrap_cluster(cluster, one_crash_per_stage(CHAOS_SEED))
    _faulted_vs_clean(qname, opts, cluster_fn=lambda: chaos)
    _assert_no_leaks(cluster)


def test_join_chain_resubmission_zero_new_traces():
    """Resubmitting an identical join-chain query through the same cluster
    performs ZERO new XLA compiles (every stage's fingerprint is stable,
    so every worker serves its compiled program from cache)."""
    ctx = _mkctx()
    sql = _q("q5")
    cluster = InMemoryCluster(4)
    base = _run(ctx, sql, cluster)
    t0 = trace_count()
    again = _run(ctx, sql, cluster)
    assert trace_count() == t0, (
        "resubmitting a join-chain query re-traced XLA programs"
    )
    _assert_frames_identical(again, base, "q5-resubmit")
