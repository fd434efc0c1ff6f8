"""Cost model: symbolic per-operator complexity evaluated against statistics.

The reference's `src/distributed_planner/statistics/` builds symbolic
complexity expressions per operator (Constant/Linear/Log/Plus/Multiply,
`complexity.rs:3-33`), evaluates them against plan statistics into a
`Cost{cpu, memory, network}` in bytes (`cost.rs`), with Trino-style
per-datatype width estimates (`default_bytes_for_datatype.rs`). The adaptive
planner sizes stage task counts from that cost (`prepare_dynamic_plan.rs`).

Same architecture here, adapted to the TPU operator set: the CPU dimension
becomes "device work" (rows processed through fused kernels), memory is
padded HBM bytes (capacity-based, matching our static-shape model), and
network is ICI/DCN bytes crossing exchanges (broadcast multiplies by the
consumer task count exactly like `complexity_network.rs`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from datafusion_distributed_tpu.plan.exchanges import (
    BroadcastExchangeExec,
    CoalesceExchangeExec,
    PartitionReplicatedExec,
    ShuffleExchangeExec,
)
from datafusion_distributed_tpu.plan.joins import (
    CrossJoinExec,
    HashJoinExec,
    UnionExec,
)
from datafusion_distributed_tpu.plan.physical import (
    ExecutionPlan,
    FilterExec,
    HashAggregateExec,
    LimitExec,
    MemoryScanExec,
    ParquetScanExec,
    ProjectionExec,
    SortExec,
)
from datafusion_distributed_tpu.schema import DataType, Schema


# Trino-style per-datatype byte widths (default_bytes_for_datatype.rs)
_BYTES = {
    DataType.INT32: 4,
    DataType.INT64: 8,
    DataType.FLOAT32: 4,
    DataType.FLOAT64: 8,
    DataType.BOOL: 1,
    DataType.DATE32: 4,
    DataType.STRING: 16,  # dictionary code + amortized dictionary share
}


def row_width(schema: Schema) -> int:
    return sum(_BYTES[f.dtype] + (1 if f.nullable else 0) for f in schema.fields)


@dataclass
class Complexity:
    """Symbolic complexity: cost = constant + linear*n + nlogn*n*log2(n)."""

    constant: float = 0.0
    linear: float = 0.0
    nlogn: float = 0.0

    def evaluate(self, n: float) -> float:
        import math

        logn = math.log2(max(n, 2.0))
        return self.constant + self.linear * n + self.nlogn * n * logn

    def __add__(self, other: "Complexity") -> "Complexity":
        return Complexity(
            self.constant + other.constant,
            self.linear + other.linear,
            self.nlogn + other.nlogn,
        )


@dataclass
class Cost:
    """Device work / HBM / interconnect, all in bytes (cost.rs analogue)."""

    compute: float = 0.0
    memory: float = 0.0
    network: float = 0.0

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(
            self.compute + other.compute,
            self.memory + other.memory,
            self.network + other.network,
        )


@dataclass
class PlanStatistics:
    """Estimated (or sampled) row counts per node, keyed by node_id; the
    runtime-statistics attachment point for the adaptive planner."""

    rows: dict  # node_id -> float estimated rows

    def rows_of(self, node: ExecutionPlan, default: float) -> float:
        return self.rows.get(node.node_id, default)


def estimate_rows(plan: ExecutionPlan, stats: Optional[PlanStatistics] = None) -> float:
    """Bottom-up cardinality estimate (CardinalityEffect analogue: filters
    shrink, joins keep the probe side, aggregates dedupe). Planner-stamped
    NDV statistics (`ExecutionPlan.est_rows` / `.est_selectivity`, from the
    catalog's sampled NDV — the same statistics that drive join/agg hash
    sizing) take precedence over the blanket heuristics."""
    if stats is not None and plan.node_id in stats.rows:
        return stats.rows[plan.node_id]
    if isinstance(plan, (MemoryScanExec,)):
        return float(sum(int(t.num_rows) for t in plan.tasks))
    if isinstance(plan, ParquetScanExec):
        return float(plan.capacity)
    if isinstance(plan, FilterExec):
        n = estimate_rows(plan.child, stats)
        sel = plan.est_selectivity
        return n * sel if sel is not None else n / 3.0
    if isinstance(plan, (ProjectionExec, LimitExec)):
        child = plan.children()[0]
        n = estimate_rows(child, stats)
        if isinstance(plan, LimitExec):
            return min(n, float(plan.fetch))
        return n
    if isinstance(plan, HashAggregateExec):
        n = estimate_rows(plan.child, stats)
        if not plan.group_names:
            return 1.0
        if plan.est_rows is not None:
            return max(min(plan.est_rows, n), 1.0)
        return max(n ** 0.5, 1.0)
    if isinstance(plan, HashJoinExec):
        p = estimate_rows(plan.probe, stats)
        if plan.join_type in ("semi", "anti"):
            return p / 2.0
        # expanding joins (many-to-many keys) emit more than probe rows;
        # the planner's expansion_factor is the sizing hint for exactly
        # that fanout — ignoring it here would systematically undercut
        # row-estimate-capped hash sizing above such joins
        return p * max(float(getattr(plan, "expansion_factor", 1.0)), 1.0)
    if isinstance(plan, CrossJoinExec):
        return estimate_rows(plan.left, stats) * estimate_rows(plan.right, stats)
    if isinstance(plan, UnionExec):
        return sum(estimate_rows(c, stats) for c in plan.children())
    if isinstance(plan, SortExec):
        n = estimate_rows(plan.child, stats)
        return min(n, float(plan.fetch)) if plan.fetch else n
    if plan.children():
        return max(estimate_rows(c, stats) for c in plan.children())
    return 1000.0


def operator_complexity(plan: ExecutionPlan) -> Complexity:
    """Per-operator symbolic device-work model in terms of OUTPUT rows
    (complexity_cpu.rs analogue for the single-input shape). Multi-input
    operators (joins) get their exact input-row shapes in
    `operator_compute_rows` — this single-n view remains for callers that
    only carry one cardinality."""
    if isinstance(plan, (MemoryScanExec, ParquetScanExec)):
        return Complexity(linear=1.0)
    if isinstance(plan, (FilterExec, ProjectionExec, LimitExec)):
        return Complexity(linear=1.0)
    if isinstance(plan, HashAggregateExec):
        return Complexity(linear=3.0)  # hash + claim rounds + scatter
    if isinstance(plan, HashJoinExec):
        return Complexity(linear=4.0)  # build + probe + expand + gather
    if isinstance(plan, CrossJoinExec):
        return Complexity(linear=8.0)
    if isinstance(plan, SortExec):
        return Complexity(nlogn=1.0)
    return Complexity(linear=1.0)


def operator_compute_rows(
    plan: ExecutionPlan, stats: Optional[PlanStatistics] = None
) -> float:
    """Row-ops this operator performs, shaped per the reference's per-op
    CPU model (`complexity_cpu.rs:5-20` cites the DataFusion internals the
    shapes come from):

      hash join   O(n_build + n_probe)   build pass + probe pass
      NLJ/cross   O(n_left * n_right)    every pair compared
      hash agg    rounds * n             claim-loop rounds over the input
      sort        n log2 n               bitonic/radix device sort
      window      n log2 n               partition sort dominates
      elementwise n                      filter/project/limit/scan
    """
    import math

    if isinstance(plan, HashJoinExec):
        b = estimate_rows(plan.build, stats)
        p = estimate_rows(plan.probe, stats)
        return b + p
    if isinstance(plan, CrossJoinExec):
        return (estimate_rows(plan.left, stats)
                * estimate_rows(plan.right, stats))
    if isinstance(plan, HashAggregateExec):
        n = estimate_rows(plan.child, stats)
        # claim-loop rounds grow with load factor: ~3 passes in the
        # steady state (hash, claim, scatter) — see ops/aggregate.py
        return 3.0 * n
    if isinstance(plan, SortExec):
        n = estimate_rows(plan.child, stats)
        return n * math.log2(max(n, 2.0))
    from datafusion_distributed_tpu.plan.window_exec import WindowExec

    if isinstance(plan, WindowExec):
        n = estimate_rows(plan.child, stats)
        return n * math.log2(max(n, 2.0))
    if isinstance(plan, UnionExec):
        return sum(estimate_rows(c, stats) for c in plan.children())
    if plan.children():
        return max(estimate_rows(c, stats) for c in plan.children())
    return estimate_rows(plan, stats)


def calculate_cost(
    plan: ExecutionPlan, stats: Optional[PlanStatistics] = None
) -> Cost:
    """Total cost of a (sub)plan: the `calculate_cost` entry point
    (cost.rs:27) — compute from the per-op input-row shapes
    (operator_compute_rows), memory from padded HBM capacities, network
    from exchange bytes; broadcast multiplies by consumer task count
    (complexity_network.rs:2-22)."""
    total = Cost()
    for c in plan.children():
        total = total + calculate_cost(c, stats)
    n = estimate_rows(plan, stats)
    width = row_width(plan.schema())
    work = operator_compute_rows(plan, stats) * width
    mem = float(plan.output_capacity()) * width
    net = 0.0
    if isinstance(plan, ShuffleExchangeExec):
        net = n * width
    elif isinstance(plan, BroadcastExchangeExec):
        net = n * width * plan.num_tasks
    elif isinstance(plan, (CoalesceExchangeExec,)):
        net = n * width * plan.num_tasks  # all_gather implementation
    elif isinstance(plan, PartitionReplicatedExec):
        net = 0.0
    return total + Cost(compute=work, memory=mem, network=net)


def stage_cost(
    head: ExecutionPlan, stats: Optional[PlanStatistics] = None
) -> Cost:
    """Cost of ONE stage: the subtree under ``head`` truncated at exchange
    boundaries — nodes below a boundary belong to producer stages and were
    already paid for (the per-stage cost of
    `prepare_dynamic_plan.rs:40-59`). The boundary's own network
    contribution is included; attach measured runtime rows for boundary
    nodes via ``stats`` (LoadInfo -> statistics, `:111-141`)."""
    total = Cost()

    def node_cost(node: ExecutionPlan) -> Cost:
        n = estimate_rows(node, stats)
        width = row_width(node.schema())
        work = operator_compute_rows(node, stats) * width
        try:
            mem = float(node.output_capacity()) * width
        except Exception:
            mem = n * width
        net = 0.0
        if isinstance(node, ShuffleExchangeExec):
            net = n * width
        elif isinstance(node, BroadcastExchangeExec):
            net = n * width * node.num_tasks
        elif isinstance(node, CoalesceExchangeExec):
            net = n * width * node.num_tasks
        return Cost(compute=work, memory=mem, network=net)

    def walk(node: ExecutionPlan) -> None:
        nonlocal total
        total = total + node_cost(node)
        if getattr(node, "is_exchange", False) and node is not head:
            return  # producer stage: costed when ITS stage was decided
        for c in node.children():
            walk(c)

    walk(head)
    return total


def compute_based_task_count(
    cost: Cost,
    bytes_per_task_per_second: float,
    max_tasks: int,
    target_seconds: float = 1.0,
) -> int:
    """Adaptive task sizing (prepare_dynamic_plan.rs:60-69 analogue):
    tasks = ceil(compute_bytes / bytes_per_task_per_second / target) clamped
    to [1, max_tasks]."""
    import math

    t = math.ceil(cost.compute / max(bytes_per_task_per_second, 1.0) / target_seconds)
    return max(1, min(t, max_tasks))


@dataclass
class ExchangeReduction:
    """Predicted effect of aggregating BELOW an exchange instead of above
    it, from sampled key-distribution statistics (the decision input of
    the partial-aggregate push-down — *Chasing Similarity*'s
    distribution-aware aggregation placement)."""

    rows_in: float  # raw rows that would cross without the push-down
    rows_out: float  # partial-state rows that cross with it
    rows_per_task: float  # expected distinct groups per producer task
    reduction: float  # 1 - rows_out/rows_in (0 = no win, ->1 = collapse)


def expected_distinct(n: float, ndv: float) -> float:
    """Expected number of DISTINCT values observed in ``n`` draws from a
    uniform domain of ``ndv`` values: ndv * (1 - (1 - 1/ndv)^n) — the
    standard coupon-collector partial-coverage estimate. This is what
    makes the push-down *distribution-aware*: a producer task holding
    rows/t raw rows emits at most this many partial groups, so low-NDV
    keys collapse (q1's 4 groups) while high-NDV keys barely shrink and
    the push-down is skipped (pure compute overhead)."""
    import math

    n = max(float(n), 0.0)
    ndv = max(float(ndv), 1.0)
    if n <= 0:
        return 0.0
    # log-space for numerical stability at large n/ndv
    return ndv * -math.expm1(n * math.log1p(-1.0 / ndv)) if ndv > 1 \
        else 1.0


def predict_partial_agg_reduction(
    rows_in: float, ndv: float, t_producer: int
) -> ExchangeReduction:
    """Rows crossing a shuffle with vs without a pre-exchange partial
    aggregate: each of ``t_producer`` tasks holds ~rows_in/t raw rows and
    emits `expected_distinct(rows_in/t, ndv)` partial states. The NDV
    comes from the catalog's sampled statistics (the `est_rows` the
    planner stamps on aggregates) — the same NDV samples that size hash
    tables."""
    t = max(int(t_producer), 1)
    rows_in = max(float(rows_in), 0.0)
    per_task = expected_distinct(rows_in / t, ndv)
    rows_out = min(per_task * t, rows_in)
    reduction = 1.0 - (rows_out / rows_in) if rows_in > 0 else 0.0
    return ExchangeReduction(
        rows_in=rows_in, rows_out=rows_out, rows_per_task=per_task,
        reduction=max(reduction, 0.0),
    )


def plan_device_bytes(plan) -> int:
    """Coarse upper bound on one program's device-buffer footprint:
    sum over nodes of output_capacity * row_width. Used by the
    overflow-retry guard: each retry widens capacity factors 4x, and a
    few compounding retries can plan buffers beyond physical memory —
    the guard abandons the retry with a clear overflow error instead of
    letting dispatch fail with an opaque allocator error (observed: q2
    SF0.5 adaptive tier, ~100GB planned after two widenings)."""
    total = 0
    for node in plan.collect(lambda _n: True):
        try:
            w = row_width(node.schema())
        except Exception:
            w = 8
        try:
            cap = int(node.output_capacity())
        except Exception:
            cap = 0
        total += cap * max(w, 1)
    return total
