"""Adaptive planning + cost model tests (reference §2.1 statistics, §2.3
dynamic mode)."""

import numpy as np
import pytest

from datafusion_distributed_tpu import precision as _precision

# f32 compute in tpu precision mode: summation-order differences are ~eps
FLOAT_RTOL = _precision.test_rtol()

import pyarrow as pa

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.plan.physical import (
    FilterExec,
    HashAggregateExec,
    MemoryScanExec,
    execute_plan,
)
from datafusion_distributed_tpu.plan.expressions import BinaryOp, Col, Literal
from datafusion_distributed_tpu.planner.adaptive import (
    LoadInfo,
    SamplerExec,
    collect_load_info,
    insert_samplers,
    resize_for_inputs,
)
from datafusion_distributed_tpu.planner.distributed import (
    DistributedConfig,
    distribute_plan,
)
from datafusion_distributed_tpu.planner.statistics import (
    Complexity,
    Cost,
    calculate_cost,
    compute_based_task_count,
    estimate_rows,
    row_width,
)
from datafusion_distributed_tpu.runtime.coordinator import (
    AdaptiveCoordinator,
    InMemoryCluster,
)
from datafusion_distributed_tpu.schema import DataType


def _plan(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    arrow = pa.table({"k": rng.integers(0, 12, n), "v": rng.normal(size=n)})
    t = arrow_to_table(arrow)
    scan = MemoryScanExec([t], t.schema())
    filt = FilterExec(BinaryOp(">", Col("v"), Literal(0.0, DataType.FLOAT64)),
                      scan)
    return HashAggregateExec(
        "single", ["k"], [AggSpec("sum", "v", "sv"),
                          AggSpec("count_star", None, "n")], filt,
    ), arrow


def test_cost_model_basics():
    plan, _ = _plan()
    rows = estimate_rows(plan)
    assert 1 <= rows <= 3000
    cost = calculate_cost(plan)
    assert cost.compute > 0 and cost.memory > 0
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=4))
    dcost = calculate_cost(dplan)
    assert dcost.network > 0  # exchanges add interconnect bytes
    assert Complexity(nlogn=1.0).evaluate(1024) == 1024 * 10
    assert compute_based_task_count(Cost(compute=1e9), 1e8, 8) == 8
    assert compute_based_task_count(Cost(compute=1e5), 1e8, 8) == 1


def test_collect_load_info():
    arrow = pa.table({
        "k": pa.array([1, 1, 2, None], type=pa.int64()),
        "s": ["a", "b", "a", "c"],
    })
    t = arrow_to_table(arrow)
    info = collect_load_info([t])
    assert info.rows == 4
    assert info.ndv["k"] == 2  # nulls excluded
    assert info.ndv["s"] == 3
    assert abs(info.null_frac["k"] - 0.25) < 1e-9
    assert info.bytes == 4 * row_width(t.schema())


def test_sampler_exec_records_metrics():
    from datafusion_distributed_tpu.runtime.metrics import MetricsStore

    plan, arrow = _plan(500)
    wrapped = SamplerExec(plan)
    store = MetricsStore()
    execute_plan(wrapped, metrics_store=store, task_label="task0")
    agg = store.aggregated()
    assert agg[wrapped.node_id]["sampled_rows"] == 12  # 12 groups
    assert agg[wrapped.node_id]["sampled_bytes"] > 0


def test_insert_samplers_under_exchanges():
    plan, _ = _plan()
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=4))
    sampled = insert_samplers(dplan)
    s = sampled.display_tree()
    assert "Sampler" in s


def test_resize_for_inputs_shrinks_slots():
    plan, _ = _plan()
    info = LoadInfo(rows=100, bytes=100 * 16, ndv={"k": 12})
    # the aggregate references materialized __g columns in distributed form;
    # use the raw plan whose group col is "k"
    resized = resize_for_inputs(plan, info)
    assert resized.num_slots <= 64  # 12 ndv * 2 headroom -> 32
    assert resized.num_slots < plan.num_slots


def test_adaptive_coordinator_matches_single():
    plan, arrow = _plan(4000, seed=3)
    single = execute_plan(plan).to_pandas().sort_values("k").reset_index(drop=True)
    dplan = distribute_plan(plan, DistributedConfig(num_tasks=4))
    cluster = InMemoryCluster(2)
    coord = AdaptiveCoordinator(resolver=cluster, channels=cluster)
    got = coord.execute(dplan).to_pandas().sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(got["k"], single["k"])
    np.testing.assert_allclose(got["sv"], single["sv"], rtol=FLOAT_RTOL,
                               atol=1e-4)
    np.testing.assert_array_equal(got["n"], single["n"])


def test_adaptive_overlap_partial_decision():
    """Mid-execution adaptive planning (the reference's overlap of
    prepare_dynamic_plan with execution, `prepare_dynamic_plan.rs:111-141`):
    with 4 concurrent producer tasks, the consumer's LoadInfo freezes from
    an extrapolated PARTIAL sample — `partial_decisions` records (done,
    total) with done < total, proving the sizing decision predates producer
    completion — and the result still matches single-node."""
    import pandas as pd

    from datafusion_distributed_tpu.sql.context import SessionContext

    rng = np.random.default_rng(7)
    n = 20000
    arrow = pa.table({"k": rng.integers(0, 50, n).astype("int64"),
                      "v": rng.normal(size=n)})
    ctx = SessionContext()
    ctx.register_arrow("t", arrow)
    ctx.config.distributed_options["bytes_per_task"] = 1  # force 4-way split
    df = ctx.sql("select k, sum(v) sv, count(*) c from t group by k")
    single = df.to_pandas().sort_values("k").reset_index(drop=True)
    cluster = InMemoryCluster(4)
    coord = AdaptiveCoordinator(resolver=cluster, channels=cluster)
    got = df._strip_quals(
        df.collect_coordinated_table(coordinator=coord, num_tasks=4)
    ).to_pandas()
    got.columns = list(single.columns)
    got = got.sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(got["k"], single["k"])
    # sums of ~400 standard normals can land near zero, where rtol alone
    # rejects benign f32 accumulation-order differences (the static
    # coordinator shows the same 2e-5 deltas)
    np.testing.assert_allclose(got["sv"], single["sv"], rtol=FLOAT_RTOL,
                               atol=1e-3)
    np.testing.assert_array_equal(got["c"], single["c"])
    assert coord.partial_decisions, (
        "no consumer sizing decision was made from partial producer output"
    )
    for done, total in coord.partial_decisions.values():
        assert 0 < done < total


def test_coshuffled_join_stage_adapts_shared_count():
    """A join stage fed by TWO shuffles re-decides its SHARED task count at
    runtime (the reference re-runs boundary injection per stage,
    `prepare_dynamic_plan.rs:26-141`): small inputs shrink both feeds to
    the same adapted count; large inputs keep more tasks. Both sides MUST
    agree or `hash % t` co-partitioning breaks — verified by result parity
    and by the recorded per-stage decisions."""
    import pandas as pd

    from datafusion_distributed_tpu.sql.context import SessionContext

    def run(n_rows):
        rng = np.random.default_rng(7)
        ctx = SessionContext()
        ctx.register_arrow("a", pa.table({
            "k": rng.integers(0, 40, n_rows),
            "v": rng.normal(size=n_rows),
        }))
        # unique build keys: join output stays n_rows (a many-to-many
        # build would blow up the single-node oracle's fan-out)
        ctx.register_arrow("b", pa.table({
            "k": np.arange(40),
            "w": rng.normal(size=40),
        }))
        # above the broadcast threshold so the join co-shuffles both sides
        ctx.config.distributed_options["broadcast_joins"] = False
        ctx.config.distributed_options["bytes_per_task"] = 1
        df = ctx.sql(
            "select a.k, sum(a.v) sv, sum(b.w) sw from a join b "
            "on a.k = b.k group by a.k order by a.k"
        )
        cluster = InMemoryCluster(2)
        coord = AdaptiveCoordinator(
            resolver=cluster, channels=cluster, bytes_per_task=1 << 16
        )
        got = df._strip_quals(
            df.collect_coordinated_table(coordinator=coord, num_tasks=4)
        ).to_pandas()
        exp = df.to_pandas()
        np.testing.assert_array_equal(got["k"].to_numpy(),
                                      exp["k"].to_numpy())
        # atol scaled to the data: group sums reach ~3e3, so 0.02 is
        # ~7e-6 of the column magnitude — zero-mean sums near 0 are where
        # rtol-only comparison of equally-f32-accurate layouts fails
        np.testing.assert_allclose(got["sv"], exp["sv"], rtol=FLOAT_RTOL,
                                   atol=2e-2)
        np.testing.assert_allclose(got["sw"], exp["sw"], rtol=FLOAT_RTOL,
                                   atol=2e-2)
        return coord.task_count_decisions

    small = run(200)
    large = run(60_000)

    def join_group(decisions):
        # the join's feeds are the two LOWEST stage ids; later solo
        # shuffles (the post-join aggregate's) decide independently
        d = {sid: t for sid, _planned, t in decisions}
        assert len(d) >= 2, decisions
        lo = sorted(d)[:2]
        return d[lo[0]], d[lo[1]]

    ts = join_group(small)
    tl = join_group(large)
    # both feeds AGREED on one adapted count, per run
    assert ts[0] == ts[1], small
    assert tl[0] == tl[1], large
    # skinny input shrinks the stage; fat input keeps the planned width
    assert ts[0] == 1, small
    assert tl[0] == 4, large


def test_midstream_column_loadinfo():
    """The partial-sample freeze carries PER-COLUMN statistics gathered
    while the stage was still producing (the reference SamplerExec's
    NDV/null/velocity LoadInfo stream, `sampler.rs:30-42`): the predicted
    LoadInfo has column NDV and null fractions, and the decision predates
    producer completion."""
    import pyarrow as pa

    from datafusion_distributed_tpu.sql.context import SessionContext

    rng = np.random.default_rng(11)
    n = 40_000
    ctx = SessionContext()
    vals = rng.normal(size=n)
    vals[rng.random(n) < 0.1] = np.nan
    ctx.register_arrow("t", pa.table({
        "k": rng.integers(0, 64, n),
        "v": pa.array(vals, from_pandas=True),  # ~10% nulls
    }))
    ctx.config.distributed_options["bytes_per_task"] = 1
    df = ctx.sql("select k, sum(v) s, count(*) c from t group by k order by k")
    cluster = InMemoryCluster(2)
    coord = AdaptiveCoordinator(resolver=cluster, channels=cluster,
                                sample_fraction=0.25)
    got = df._strip_quals(
        df.collect_coordinated_table(coordinator=coord, num_tasks=8)
    ).to_pandas()
    exp = df.to_pandas()
    np.testing.assert_array_equal(got["k"].to_numpy(), exp["k"].to_numpy())
    np.testing.assert_allclose(
        got["s"].to_numpy(), exp["s"].to_numpy(), rtol=FLOAT_RTOL,
        equal_nan=True,
    )
    assert coord.partial_decisions, "no mid-execution freeze happened"
    for done, total in coord.partial_decisions.values():
        assert done < total
    with_ndv = [
        (sid, i) for sid, i in coord._predicted.items() if i.ndv
    ]
    assert with_ndv, "predicted LoadInfo carried no per-column statistics"
    sid, info = with_ndv[0]
    # frozen per-column NDVs stay RAW (what the partial sample observed);
    # the producer-coverage factor lives SEPARATELY in info.ndv_scale
    # (total/done) and is applied once to the group-key tuple product by
    # resize_for_inputs — scaling each column here would compound the
    # factor across multi-key groups. The 64-distinct-key group column
    # bounds every raw observation.
    assert any(1 <= v <= 64 for v in info.ndv.values()), info.ndv
    done, total = coord.partial_decisions[sid]
    assert info.ndv_scale == pytest.approx(total / done), (
        info.ndv_scale, done, total)
    assert info.ndv_scale > 1.0  # a partial freeze implies done < total
    assert info.null_frac, "no null fractions sampled"
    assert info.rows_per_s > 0 and info.bytes_per_s > 0


def test_targeted_overflow_widening():
    """An overflow names its program's capacity-capable nodes; the retry
    must widen ONLY the implicated knobs. Global widening is how one
    undersized aggregate table compounded into a ~916GB plan (q2 SF0.5
    adaptive) that tripped the byte-budget guard instead of converging."""
    from datafusion_distributed_tpu.planner.distributed import (
        DistributedConfig,
    )
    from datafusion_distributed_tpu.runtime.errors import (
        CapacityOverflowError,
    )
    from datafusion_distributed_tpu.sql.context import _widen_for_overflow
    from datafusion_distributed_tpu.sql.planner import PlannerConfig

    p = PlannerConfig()
    d = DistributedConfig(num_tasks=4)

    agg = CapacityOverflowError(
        "hash table overflow in plan (nodes: ['HashAggregate']); "
        "re-plan with more slots"
    )
    p2, d2 = _widen_for_overflow(p, d, agg)
    assert p2.agg_slot_factor == p.agg_slot_factor * 4
    assert p2.join_expansion_factor == p.join_expansion_factor
    assert d2.shuffle_skew_factor == d.shuffle_skew_factor

    js = CapacityOverflowError(
        "exchange/hash capacity overflow on mesh (nodes: "
        "['HashJoin', 'ShuffleExchange']); re-plan with more slots"
    )
    p3, d3 = _widen_for_overflow(p, d, js)
    assert p3.join_expansion_factor == p.join_expansion_factor * 4
    assert p3.agg_slot_factor == p.agg_slot_factor
    assert d3.shuffle_skew_factor == d.shuffle_skew_factor * 4

    # no parseable node list -> the pre-targeting widen-everything behavior
    bare = CapacityOverflowError("hash table overflow somewhere")
    p4, d4 = _widen_for_overflow(p, d, bare)
    assert p4.agg_slot_factor == p.agg_slot_factor * 4
    assert p4.join_expansion_factor == p.join_expansion_factor * 4
    assert d4.shuffle_skew_factor == d.shuffle_skew_factor * 4

    # parsed list with NO recognized label (future node class): must widen
    # everything, not nothing — else every retry re-runs the same plan
    odd = CapacityOverflowError(
        "hash table overflow in plan (nodes: ['TopK']); re-plan"
    )
    p5, d5 = _widen_for_overflow(p, d, odd)
    assert p5.agg_slot_factor == p.agg_slot_factor * 4
    assert p5.join_expansion_factor == p.join_expansion_factor * 4
    assert d5.shuffle_skew_factor == d.shuffle_skew_factor * 4

    # single-process collect has no distributed config: a shuffle-only
    # list must still widen the planner factors, not no-op every retry
    shuf_only = CapacityOverflowError(
        "hash table overflow in plan (nodes: ['ShuffleExchange']); re-plan"
    )
    p6, d6 = _widen_for_overflow(p, None, shuf_only)
    assert d6 is None
    assert p6.agg_slot_factor == p.agg_slot_factor * 4
    assert p6.join_expansion_factor == p.join_expansion_factor * 4

    # force_all (the loops' LAST widening): targeting serializes knob
    # discovery, so the final attempt widens everything applicable
    p7, d7 = _widen_for_overflow(p, d, agg, force_all=True)
    assert p7.agg_slot_factor == p.agg_slot_factor * 4
    assert p7.join_expansion_factor == p.join_expansion_factor * 4
    assert d7.shuffle_skew_factor == d.shuffle_skew_factor * 4


def test_pinned_headroom_survives_inner_success():
    """Scalar subqueries execute through the SAME coordinator as the outer
    query; a successful inner execute must NOT reset a session-pinned
    (overflow-retry-widened) resize headroom back to base — that reset made
    q11's overflowing group-by re-run at base headroom on every retry."""
    import pyarrow as pa

    from datafusion_distributed_tpu.sql.context import SessionContext

    rng = np.random.default_rng(3)
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({
        "k": rng.integers(0, 8, 2000), "v": rng.normal(size=2000),
    }))
    ctx.config.distributed_options["bytes_per_task"] = 1
    df = ctx.sql("select k, sum(v) s from t group by k")
    cluster = InMemoryCluster(2)
    coord = AdaptiveCoordinator(resolver=cluster, channels=cluster)
    plan = df.distributed_plan(4, coordinator=coord)

    coord.pin_overflow_headroom(attempt=2)
    pinned = coord.resize_headroom
    assert pinned == coord._base_resize_headroom * (
        coord.OVERFLOW_WIDEN_FACTOR ** 2
    )
    out = coord.execute(plan)
    assert out.num_rows == 8
    assert coord.resize_headroom == pinned, "pin was reset by a success"

    coord.release_overflow_headroom()
    coord.execute(plan)
    assert coord.resize_headroom == coord._base_resize_headroom
