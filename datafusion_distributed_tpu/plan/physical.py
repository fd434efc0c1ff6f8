"""Physical plan IR: the ExecutionPlan tree.

The reference builds on DataFusion's `ExecutionPlan` trait (async per-partition
`RecordBatch` streams; SURVEY.md L0) and inserts its distributed operators into
that tree (`/root/reference/src/execution_plans/`). The TPU re-design keeps
the *tree* (the planner passes need it) but changes the execution contract:

- an operator's `execute(ctx)` does not stream; it **traces** the whole
  per-task pipeline into one XLA computation over padded Tables. XLA fusion
  replaces the volcano pipeline — filter+project+partial-agg become one fused
  kernel on the device.
- per-task intra-operator partitions collapse to 1: on a TPU the chip's
  parallelism comes from XLA, not operator threads. The reference's
  partition-level parallelism maps to *tasks* (devices) instead; see
  parallel/ for the exchange operators.
- leaf scans run on the host (Parquet decode) *before* tracing; the executor
  passes their Tables in as pytree arguments so the traced function is
  shape-stable and cacheable across batches of the same capacity.

Every node computes a static `output_capacity` — the padded row bound that
makes XLA shapes static (SURVEY.md §7 hard part (c)).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
from dataclasses import dataclass, field as dc_field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from datafusion_distributed_tpu import spans
from datafusion_distributed_tpu.ops.aggregate import (
    _DENSE_MAX_DOMAIN,
    AggSpec,
    hash_aggregate,
)
from datafusion_distributed_tpu.ops.sort import (
    SortKey,
    fetch_capacity,
    limit_table,
    sort_table,
)
from datafusion_distributed_tpu.ops.table import (
    Column,
    Table,
    concat_tables,
    is_host_backed,
    round_up_pow2,
)
from datafusion_distributed_tpu.plan.expressions import (
    PhysicalExpr,
    expr_to_column,
)
from datafusion_distributed_tpu.schema import DataType, Field, Schema


# ---------------------------------------------------------------------------
# Task context
# ---------------------------------------------------------------------------


@dataclass
class DistributedTaskContext:
    """Which task of a stage this execution is (reference:
    `src/stage.rs` DistributedTaskContext)."""

    task_index: int = 0
    task_count: int = 1


@dataclass
class ExecContext:
    """Carried through `execute` tracing."""

    task: DistributedTaskContext
    inputs: dict[int, Table]  # leaf node_id -> loaded device Table
    # (node label, traced bool) of every node that can outgrow a planned
    # capacity, and of every 32-bit accumulator that can leave its exact
    # range: a re-plan with wider capacities cures the first kind only
    capacity_flags: list = dc_field(default_factory=list)
    precision_flags: list = dc_field(default_factory=list)
    config: dict = dc_field(default_factory=dict)
    # traced per-node metrics: (node_id, metric_name, traced scalar). The
    # executor returns these as program outputs and stitches them into a
    # MetricsStore host-side (runtime/metrics.py).
    metrics: list = dc_field(default_factory=list)
    # exchange-node memoization (node_id -> Table): collectives must execute
    # exactly once per program and OUTSIDE any lax.cond (all tasks
    # participate unconditionally); IsolatedArmExec relies on this to
    # pre-execute an arm's exchanges before conditioning its local compute
    exchange_cache: dict = dc_field(default_factory=dict)
    # what the trace counted, by the names of `spans.PROGRAM_COUNTERS`
    counters: dict = dc_field(
        default_factory=lambda: dict.fromkeys(spans.PROGRAM_COUNTERS, 0)
    )

    def record_overflow(self, node: "ExecutionPlan", flag) -> None:
        self.capacity_flags.append((node.label(), flag))

    def record_precision_error(self, node: "ExecutionPlan", flag) -> None:
        self.precision_flags.append((node.label(), flag))

    def record_metric(self, node: "ExecutionPlan", name: str, value) -> None:
        if self.config.get("collect_metrics", True):
            self.metrics.append((node.node_id, name, value))

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] += n

    def count_largest(self, name: str, n: int) -> None:
        """A counter that keeps the largest value seen, not the sum."""
        self.counters[name] = max(self.counters[name], n)

    def child(self) -> "ExecContext":
        """A context to trace a part of the plan apart from this one (a
        `lax.cond` branch, a probe for shapes): this context's task,
        inputs, config and exchange cache, and flags, metrics and
        counters of its own."""
        return ExecContext(
            task=self.task, inputs=self.inputs, config=self.config,
            exchange_cache=self.exchange_cache,
        )

    def traced_values(self) -> tuple:
        """The traced scalars this context recorded, capacity flags,
        precision flags, then metrics: what a `lax.cond` branch must
        return for them to leave it."""
        return (
            tuple(f for _, f in self.capacity_flags)
            + tuple(f for _, f in self.precision_flags)
            + tuple(v for _, _, v in self.metrics)
        )

    def adopt(self, like: "ExecContext", values) -> None:
        """Record here what the child context ``like`` recorded, with
        ``values`` (in `traced_values` order) in place of its scalars."""
        values = iter(values)
        self.capacity_flags.extend(
            (label, next(values)) for label, _ in like.capacity_flags
        )
        self.precision_flags.extend(
            (label, next(values)) for label, _ in like.precision_flags
        )
        self.metrics.extend(
            (nid, name, next(values)) for nid, name, _ in like.metrics
        )

# trace-time only: the pre-order position of every node of the plan being
# traced on this thread (`traced_positions`), for `node_scope`
_POSITIONS = threading.local()

#: `ProgramTrace.metric_names` entry of a program's own row count: the
#: root (pre-order position 0) records ``output_rows`` as every node does
_ROOT_ROWS = (0, "output_rows")


@contextlib.contextmanager
def traced_positions(plan: "ExecutionPlan"):
    """-> node_id -> pre-order position in ``plan``: the address that is
    the same for fingerprint-equal plan copies, where node ids are minted
    per object. Inside the ``with`` (the trace of the plan on this thread)
    it is also what `node_scope` names a node by."""
    pos_of = {
        n.node_id: i for i, n in enumerate(plan.collect(lambda _n: True))
    }
    _POSITIONS.of = pos_of
    try:
        yield pos_of
    finally:
        _POSITIONS.of = {}


def node_scope(node: "ExecutionPlan") -> str:
    """The `jax.named_scope` of a plan node inside the compiled program:
    ``<node class>.<pre-order position>`` (`HashAggregateExec.2`), never
    a node id or anything else that differs between fingerprint-equal
    plans; the bare class where no positions are set."""
    pos = getattr(_POSITIONS, "of", {}).get(node.node_id)
    name = type(node).__name__
    return name if pos is None else f"{name}.{pos}"

_NODE_COUNTER = itertools.count()


# ---------------------------------------------------------------------------
# Base node
# ---------------------------------------------------------------------------


class ExecutionPlan:
    """Base of the physical plan tree."""

    #: statistics annotations stamped by the SQL planner from catalog NDV
    #: (the role DataFusion table-provider statistics play for the
    #: reference's cost model): estimated output rows / filter selectivity.
    #: Consumed by planner/statistics.estimate_rows; preserved across
    #: with_new_children rebuilds by the __init_subclass__ hook below.
    est_rows: "float | None" = None
    est_selectivity: "float | None" = None
    #: runtime-adaptivity annotations stamped by the distributed planner's
    #: partial-aggregate push-down pass: marks a "partial" aggregate whose
    #: measured reduction the coordinator may probe and bail out of
    #: (runtime/adaptivity.py). Coordinator-side only — never fingerprinted,
    #: never serialized — but must survive the with_new_children rebuilds
    #: the coordinator performs while resolving nested exchange scans.
    bailout_candidate: "bool | None" = None
    predicted_partial_rows: "int | None" = None
    #: multiway-join fusion annotations (planner/distributed
    #: _multiway_fusion_pass): a fused MultiwayHashJoinExec the coordinator
    #: may bail back to its binary chain when measured build sizes diverge.
    multiway_bailout_candidate: "bool | None" = None
    #: shuffles the fusion pass deleted building this node (identity
    #: re-partitions); surfaced in EXPLAIN and asserted by tests
    multiway_deleted_exchanges: "int | None" = None
    #: global-hash-agg annotation (_inject_aggregate): marks a single-mode
    #: aggregate the planner chose over partial+final because predicted NDV
    #: was too high for partial states to shrink the exchange; guards the
    #: push-down pass from re-rewriting it.
    global_agg_selected: "bool | None" = None

    #: annotations the __init_subclass__ hook carries across rebuilds
    _PRESERVED_ANNOTATIONS = (
        "est_rows", "est_selectivity",
        "bailout_candidate", "predicted_partial_rows",
        "multiway_bailout_candidate",
        "multiway_deleted_exchanges", "global_agg_selected",
    )

    def __init__(self) -> None:
        self.node_id = next(_NODE_COUNTER)

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        impl = cls.__dict__.get("with_new_children")
        if impl is None:
            return
        import functools

        @functools.wraps(impl)
        def wrapped(self, children, _impl=impl):
            n = _impl(self, children)
            if n is not self and type(n) is type(self):
                for a in self._PRESERVED_ANNOTATIONS:
                    v = getattr(self, a, None)
                    if v is not None and getattr(n, a, None) is None:
                        setattr(n, a, v)
            return n

        cls.with_new_children = wrapped

    # -- tree ---------------------------------------------------------------
    def children(self) -> list["ExecutionPlan"]:
        raise NotImplementedError

    def with_new_children(self, children: list["ExecutionPlan"]) -> "ExecutionPlan":
        raise NotImplementedError

    # -- properties ---------------------------------------------------------
    def schema(self) -> Schema:
        raise NotImplementedError

    def output_capacity(self) -> int:
        raise NotImplementedError

    # -- execution ----------------------------------------------------------
    def execute(self, ctx: ExecContext) -> Table:
        """Trace this operator under its scope (`node_scope`); records
        the per-node output_rows metric (the DataFusion baseline metric
        set analogue)."""
        with jax.named_scope(node_scope(self)):
            out = self._execute(ctx)
        ctx.record_metric(self, "output_rows", out.num_rows)
        return out

    def _execute(self, ctx: ExecContext) -> Table:
        raise NotImplementedError

    def execute_masked(self, ctx: ExecContext):
        """What a consumer that works on a mask pulls (an aggregate: every
        reduction of it masks dead rows out anyway). -> (table, live,
        rows): this node's output, the ``[capacity] bool`` mask of its
        live rows, which need not sit at the front (None: the
        ``num_rows`` prefix), and their count. The default is the packed
        table; `FilterExec` and `ProjectionExec` answer without packing.
        Who pulls decides, by its type: every other consumer calls
        `execute` and gets packed rows."""
        t = self.execute(ctx)
        return t, None, t.num_rows

    def _answer_masked(self, ctx: ExecContext, work):
        """`execute`'s scope and ``output_rows`` metric for a unary node
        answered on the masked path: ``work(table, live, rows) -> (table,
        live, rows)`` over what the child answers."""
        with jax.named_scope(node_scope(self)):
            out, live, rows = work(*self.child.execute_masked(ctx))
        ctx.record_metric(self, "output_rows", rows)
        return out, live, rows

    # -- display ------------------------------------------------------------
    def label(self) -> str:
        return type(self).__name__.removesuffix("Exec")

    def display(self) -> str:
        return self.label()

    def display_tree(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.display()]
        for c in self.children():
            lines.append(c.display_tree(indent + 1))
        return "\n".join(lines)

    # -- traversal helpers --------------------------------------------------
    def transform_up(self, f: Callable[["ExecutionPlan"], "ExecutionPlan"]):
        new_children = [c.transform_up(f) for c in self.children()]
        node = self.with_new_children(new_children) if new_children else self
        return f(node)

    def transform_down(self, f: Callable[["ExecutionPlan"], "ExecutionPlan"]):
        node = f(self)
        children = [c.transform_down(f) for c in node.children()]
        return node.with_new_children(children) if children else node

    def collect(self, pred: Callable[["ExecutionPlan"], bool]) -> list["ExecutionPlan"]:
        out = [self] if pred(self) else []
        for c in self.children():
            out.extend(c.collect(pred))
        return out


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------


class PartitionedTableError(ValueError):
    """A tier that runs a plan on one device, or on the host's workers, was
    given a scan of a table registered as partitions, a partition a device
    (`Catalog.scan_exec`): it would answer over one partition."""


class MemoryScanExec(ExecutionPlan):
    """Scan over pre-loaded per-task device Tables.

    The reference's `DistributedLeafExec` holds per-task variants of a leaf
    and picks by `task_index` (`src/execution_plans/distributed_leaf.rs`);
    here each task's slice is one padded Table in `tasks`.
    """

    def __init__(self, tasks: Sequence[Table], schema: Schema,
                 pinned: bool = False, replicated: bool = False,
                 partitioned: bool = False):
        super().__init__()
        self.tasks = list(tasks)
        self._schema = schema
        # pinned: this scan is already task-specialized (holds exactly the
        # executing task's slice); ignore task_index on load
        self.pinned = pinned
        # replicated: one logical table served identically to EVERY task
        # (coalesce/broadcast exchange outputs) — load ignores task_index,
        # and the coordinator may run a stage reading only replicated scans
        # as a single task (its output is the complete result)
        self.replicated = replicated
        # partitioned: ``tasks`` are a registered table's row partitions,
        # task i's resident on device i (`Catalog.scan_exec` of a
        # `PartitionedTable`); the mesh tier uses them where they lie
        # (`runtime/mesh_executor.py place_partitions`), and a tier that
        # would run fewer tasks than partitions is refused
        self.partitioned = partitioned

    def children(self):
        return []

    def with_new_children(self, children):
        assert not children
        return self

    def schema(self):
        return self._schema

    def output_capacity(self):
        # default=8: a co-shuffled group's PLACEHOLDER scan (adaptive
        # coordinator, `_finish_shuffle`) is empty while sibling feeds
        # materialize; parents rebuilt over it during that window get a
        # floor capacity, corrected by resize_for_inputs at dispatch
        return max((t.capacity for t in self.tasks), default=8)

    def load(self, task: DistributedTaskContext) -> Table:
        if self.partitioned and task.task_count < len(self.tasks):
            raise partitioned_table_error(
                len(self.tasks), "a collect of fewer tasks than partitions")
        if self.pinned or self.replicated:
            return self.tasks[0]
        if task.task_index >= len(self.tasks):
            # Tasks beyond the data slices read nothing (the reference's
            # short coalesce groups yield empty streams the same way).
            ref = self.tasks[0]
            return Table.empty(self._schema, ref.capacity, _dicts_of(ref))
        return self.tasks[task.task_index]

    def _execute(self, ctx: ExecContext) -> Table:
        return ctx.inputs[self.node_id]

    def display(self):
        mark = " partitioned" if self.partitioned else ""
        return (f"MemoryScan tasks={len(self.tasks)}{mark} "
                f"cap={self.output_capacity()}")


def partitioned_table_error(partitions: int, tier: str) -> PartitionedTableError:
    return PartitionedTableError(
        f"a table registered as {partitions} partitions, a partition a "
        f"device, is read by the mesh tier alone: "
        f"collect_distributed(mesh=make_mesh({partitions})); {tier} would "
        "answer over one partition")


def refuse_partitioned_scans(plan: "ExecutionPlan", tier: str) -> None:
    """Raise `PartitionedTableError` where ``plan`` scans a partitioned
    table: ``tier`` (the coordinator's, the serving tier's) runs its
    tasks on the host's workers, not where the partitions lie."""
    scans = plan.collect(lambda n: getattr(n, "partitioned", False))
    if scans:
        raise partitioned_table_error(len(scans[0].tasks), tier)


class ParquetScanExec(ExecutionPlan):
    """Parquet leaf: per-task file groups decoded on the host, uploaded padded.

    Mirrors the role of DataFusion's `DataSourceExec` + the reference's
    task-specialized file-group slicing (`task_estimator.rs` scale_up path).
    """

    def __init__(
        self,
        file_groups: Sequence[Sequence[str]],  # one list of files per task
        schema: Schema,
        capacity: int,
        projection: Optional[Sequence[str]] = None,
        dictionaries: Optional[dict] = None,
    ):
        super().__init__()
        self.file_groups = [list(g) for g in file_groups]
        self._schema = schema if projection is None else schema.select(projection)
        self.projection = list(projection) if projection else None
        self.capacity = capacity
        self.dictionaries = dictionaries

    def children(self):
        return []

    def with_new_children(self, children):
        assert not children
        return self

    def schema(self):
        return self._schema

    def output_capacity(self):
        return self.capacity

    def load(self, task: DistributedTaskContext) -> Table:
        from datafusion_distributed_tpu.io.parquet import read_parquet

        files = (
            self.file_groups[task.task_index]
            if task.task_index < len(self.file_groups)
            else []
        )
        if not files:
            return Table.empty(self._schema, self.capacity, self.dictionaries)
        return read_parquet(
            files,
            columns=self.projection,
            capacity=self.capacity,
            dictionaries=self.dictionaries,
        )

    def _execute(self, ctx: ExecContext) -> Table:
        return ctx.inputs[self.node_id]

    def display(self):
        nfiles = sum(len(g) for g in self.file_groups)
        return (
            f"ParquetScan tasks={len(self.file_groups)} files={nfiles} "
            f"cap={self.capacity}"
        )


# ---------------------------------------------------------------------------
# Unary operators
# ---------------------------------------------------------------------------


class FilterExec(ExecutionPlan):
    def __init__(self, predicate: PhysicalExpr, child: ExecutionPlan):
        super().__init__()
        self.predicate = predicate
        self.child = child

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return FilterExec(self.predicate, children[0])

    def schema(self):
        return self.child.schema()

    def output_capacity(self):
        return self.child.output_capacity()

    def _keep(self, t: Table) -> jnp.ndarray:
        v = self.predicate.evaluate(t)
        return v.data.astype(jnp.bool_) & v.valid_mask()

    def _execute(self, ctx: ExecContext) -> Table:
        t = self.child.execute(ctx)
        return t.compact(self._keep(t))

    def execute_masked(self, ctx: ExecContext):
        """No compaction: the child's rows stay where they are and the
        predicate narrows the mask (stacked filters AND theirs)."""
        ctx.count("masked_filters")

        def narrow(t, live, _rows):
            keep = self._keep(t) & (t.row_mask() if live is None else live)
            return t, keep, jnp.sum(keep, dtype=jnp.int32)

        return self._answer_masked(ctx, narrow)

    def display(self):
        return f"Filter: {self.predicate.display()}"


class ProjectionExec(ExecutionPlan):
    def __init__(self, exprs: Sequence[tuple[PhysicalExpr, str]], child: ExecutionPlan):
        super().__init__()
        self.exprs = list(exprs)
        self.child = child

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return ProjectionExec(self.exprs, children[0])

    def schema(self):
        child_schema = self.child.schema()
        fields = []
        for expr, name in self.exprs:
            f = expr.output_field(child_schema)
            fields.append(Field(name, f.dtype, f.nullable))
        return Schema(fields)

    def output_capacity(self):
        return self.child.output_capacity()

    def _project(self, t: Table) -> Table:
        cols = {}
        for expr, name in self.exprs:
            cols[name] = expr_to_column(expr.evaluate(t))
        return Table(tuple(cols.keys()), tuple(cols.values()), t.num_rows)

    def _execute(self, ctx: ExecContext) -> Table:
        return self._project(self.child.execute(ctx))

    def execute_masked(self, ctx: ExecContext):
        """Elementwise, so the child's mask passes through."""
        return self._answer_masked(
            ctx, lambda t, live, rows: (self._project(t), live, rows)
        )

    def display(self):
        inner = ", ".join(f"{e.display()} AS {n}" for e, n in self.exprs)
        return f"Projection: {inner}"


class HashAggregateExec(ExecutionPlan):
    """GROUP BY over named columns (planner materializes expressions below
    via a ProjectionExec). Modes: single | partial | final, as in the
    reference's use of DataFusion AggregateMode (+ PartialReduce analogue to
    come with the distributed planner)."""

    def __init__(
        self,
        mode: str,
        group_names: Sequence[str],
        aggs: Sequence[AggSpec],
        child: ExecutionPlan,
        num_slots: Optional[int] = None,
    ):
        super().__init__()
        assert mode in ("single", "partial", "final", "partial_reduce")
        self.mode = mode
        self.group_names = list(group_names)
        self.aggs = list(aggs)
        self.child = child
        # Default table size: 2x the input bound keeps the load factor <= 0.5
        # even in the all-rows-distinct worst case, so the claim loop
        # converges well inside max_rounds (see ops/aggregate.py docstring).
        self.num_slots = num_slots or min(
            round_up_pow2(2 * max(child.output_capacity(), 16)), 1 << 20
        )
        # OUTPUT capacity: groups <= live input rows, so the packed result
        # never needs more than pow2(input capacity) — downstream operators
        # (the final sort especially) pay capacity-proportional work, and
        # slots = 2x input would hand them double-width padding for free.
        self.out_capacity = min(
            self.num_slots,
            round_up_pow2(max(child.output_capacity(), 16)),
        )

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return HashAggregateExec(
            self.mode, self.group_names, self.aggs, children[0], self.num_slots
        )

    def schema(self):
        child_schema = self.child.schema()
        fields = [child_schema.field(g) for g in self.group_names]
        for a in self.aggs:
            fields.extend(_agg_output_fields(a, child_schema, self.mode))
        return Schema(fields)

    def output_capacity(self):
        return self.out_capacity if self.group_names else self.num_slots

    def _execute(self, ctx: ExecContext) -> Table:
        # every reduction below masks dead rows out, so the input need not
        # be packed: filters and projections underneath hand their mask up
        t, live, _ = self.child.execute_masked(ctx)
        prec_flags: list = []
        direct: list = []
        scatters: list = []
        presence_from_count: list = []
        if not self.group_names:
            from datafusion_distributed_tpu.ops.aggregate import global_aggregate

            out = global_aggregate(t, self.aggs, self.mode,
                                   prec_flags=prec_flags, live=live)
            ctx.count("dense_aggregates")  # one slot: no scatter to choose
            ctx.count_largest("group_slots", 1)
        else:
            out, overflow = hash_aggregate(
                t, self.group_names, self.aggs, self.num_slots, self.mode,
                prec_flags=prec_flags, out_capacity=self.out_capacity,
                live=live, direct=direct, scatters=scatters,
                presence_from_count=presence_from_count,
            )
            ctx.record_overflow(self, overflow)
            ctx.count("direct_groupings", len(direct))
            ctx.count("dense_aggregates",
                      sum(d <= _DENSE_MAX_DOMAIN for d in direct))
            ctx.count_largest("group_slots",
                              direct[0] if direct else self.num_slots)
            ctx.count("scatter_reductions", len(scatters))
            ctx.count("presence_from_count", len(presence_from_count))
        for f in prec_flags:
            ctx.record_precision_error(self, f)
        return out

    def display(self):
        aggs = ", ".join(f"{a.func}({a.input_name or '*'})" for a in self.aggs)
        return (
            f"HashAggregate mode={self.mode} gby=[{', '.join(self.group_names)}] "
            f"aggs=[{aggs}] slots={self.num_slots}"
        )


def _agg_output_fields(a: AggSpec, child_schema: Schema, mode: str) -> list[Field]:
    from datafusion_distributed_tpu.ops.aggregate import _VARIANCE_FUNCS

    if a.func == "count_star" or a.func == "count":
        return [Field(a.output_name, DataType.INT64, nullable=False)]
    if a.func == "avg":
        if mode in ("partial", "partial_reduce"):
            return [
                Field(f"{a.output_name}__sum", DataType.FLOAT64, True),
                Field(f"{a.output_name}__count", DataType.INT64, False),
            ]
        return [Field(a.output_name, DataType.FLOAT64, True)]
    if a.func in _VARIANCE_FUNCS:
        if mode in ("partial", "partial_reduce"):
            return [
                Field(f"{a.output_name}__sum", DataType.FLOAT64, True),
                Field(f"{a.output_name}__sumsq", DataType.FLOAT64, True),
                Field(f"{a.output_name}__count", DataType.INT64, False),
            ]
        return [Field(a.output_name, DataType.FLOAT64, True)]
    if mode in ("final", "partial_reduce"):
        # Final mode consumes the partial stage's accumulator column, which
        # already carries the merged dtype under the output name.
        src = child_schema.field(a.output_name)
        return [Field(a.output_name, src.dtype, True)]
    src = child_schema.field(a.input_name) if a.input_name else None
    if a.func == "sum":
        dt = DataType.FLOAT64 if src.dtype.is_float else DataType.INT64
        return [Field(a.output_name, dt, True)]
    # min/max keep input type
    return [Field(a.output_name, src.dtype, True)]


class PartialPassthroughExec(ExecutionPlan):
    """Per-row partial-aggregation states — the bail-out form of a
    pushed-down ``HashAggregateExec(mode="partial")``. Emits, for every
    input row, the singleton accumulator a one-row group would produce
    (ops/aggregate.py `singleton_partial_states`), under the exact
    partial-mode schema, so the downstream final aggregate merges either
    operator's output interchangeably. The runtime swaps this in for the
    remaining tasks of a stage whose probed first task showed the
    sampled-NDV prediction was wrong and the partial barely reduces
    (runtime/adaptivity.py): pure elementwise work instead of a hash
    table that pays without shrinking the exchange."""

    def __init__(self, group_names: Sequence[str], aggs: Sequence[AggSpec],
                 child: ExecutionPlan):
        super().__init__()
        self.group_names = list(group_names)
        self.aggs = list(aggs)
        self.child = child

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return PartialPassthroughExec(self.group_names, self.aggs,
                                      children[0])

    def schema(self):
        child_schema = self.child.schema()
        fields = [child_schema.field(g) for g in self.group_names]
        for a in self.aggs:
            fields.extend(_agg_output_fields(a, child_schema, "partial"))
        return Schema(fields)

    def output_capacity(self):
        return self.child.output_capacity()

    def _execute(self, ctx: ExecContext) -> Table:
        from datafusion_distributed_tpu.ops.aggregate import (
            singleton_partial_states,
        )

        return singleton_partial_states(
            self.child.execute(ctx), self.group_names, self.aggs
        )

    def display(self):
        aggs = ", ".join(f"{a.func}({a.input_name or '*'})" for a in self.aggs)
        return (
            f"PartialPassthrough gby=[{', '.join(self.group_names)}] "
            f"aggs=[{aggs}]"
        )


class SortExec(ExecutionPlan):
    def __init__(self, keys: Sequence[SortKey], child: ExecutionPlan,
                 fetch: Optional[int] = None):
        super().__init__()
        self.keys = list(keys)
        self.child = child
        self.fetch = fetch

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return SortExec(self.keys, children[0], self.fetch)

    def schema(self):
        return self.child.schema()

    def output_capacity(self):
        return fetch_capacity(self.fetch, self.child.output_capacity())

    def _execute(self, ctx: ExecContext) -> Table:
        t = self.child.execute(ctx)
        out = sort_table(t, self.keys, self.fetch)
        if out.capacity < t.capacity:
            ctx.count("fetch_bounded_sorts")
        return out

    def display(self):
        ks = ", ".join(
            f"{k.name} {'ASC' if k.ascending else 'DESC'}" for k in self.keys
        )
        fetch = f" fetch={self.fetch}" if self.fetch is not None else ""
        return f"Sort: [{ks}]{fetch}"


class LimitExec(ExecutionPlan):
    def __init__(self, child: ExecutionPlan, fetch: int, skip: int = 0):
        super().__init__()
        self.child = child
        self.fetch = fetch
        self.skip = skip

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return LimitExec(children[0], self.fetch, self.skip)

    def schema(self):
        return self.child.schema()

    def output_capacity(self):
        return self.child.output_capacity()

    def _execute(self, ctx: ExecContext) -> Table:
        return limit_table(self.child.execute(ctx), self.fetch, self.skip)

    def display(self):
        skip = f" skip={self.skip}" if self.skip else ""
        return f"Limit: fetch={self.fetch}{skip}"


class CoalescePartitionsExec(ExecutionPlan):
    """N input partitions -> 1. In the per-task model a task's plan already
    yields one Table, so locally this is identity; it exists as the planner's
    stage-head marker (the reference wraps plans in CoalescePartitionsExec
    before staging, `distributed_query_planner.rs` shape pass)."""

    def __init__(self, child: ExecutionPlan):
        super().__init__()
        self.child = child

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return CoalescePartitionsExec(children[0])

    def schema(self):
        return self.child.schema()

    def output_capacity(self):
        return self.child.output_capacity()

    def _execute(self, ctx: ExecContext) -> Table:
        return self.child.execute(ctx)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


def collect_leaves(plan: ExecutionPlan) -> list[ExecutionPlan]:
    return plan.collect(lambda n: not n.children())


def execute_plan(
    plan: ExecutionPlan,
    task: Optional[DistributedTaskContext] = None,
    config: Optional[dict] = None,
    check_overflow: bool = True,
    metrics_store=None,
    task_label: Optional[str] = None,
    use_cache: bool = True,
    shared_cache: Optional[dict] = None,
    shared_key=None,
) -> Table:
    """Run a (single-task) plan: host-load leaves, trace+jit the rest once.

    The compile cache is keyed on the plan's STRUCTURAL FINGERPRINT
    (plan/fingerprint.py) — node kinds, expressions, capacities, the task
    lattice — not object identity, so a fresh submission of an identical
    query (new ``ctx.sql()`` call) reuses the compiled executable, and a
    literal-hoisted template variant reuses it with new parameter inputs
    (the analogue of the reference's task re-execution against the cached
    plan in `TaskData`, extended across queries). Plans containing nodes the
    fingerprint cannot canonicalize fall back to object-identity keying.
    When ``metrics_store`` is given, the traced
    per-node metrics are returned as program outputs, brought to the host
    on the one pull that brings the flag vector, and inserted under
    ``task_label`` with the output's row count (runtime/metrics.py
    MetricsStore protocol, ``rows_out``): the call waits on the device
    once either way, and the output itself stays on the device.

    ``shared_cache``/``shared_key`` let a caller share ONE traced program
    across *distinct plan objects of the same stage* (the worker runtime:
    every task of a stage decodes its own plan copy, but the padded-capacity
    lattice makes the traced computation task-invariant — only the leaf
    *data* differs, and that enters as a program input). The caller is
    responsible for only passing plans whose trace does not branch on
    ``task_index`` (see Worker.execute_task: IsolatedArmExec disables it);
    the structural fingerprint plus the input pytree structure +
    shapes/dtypes are appended to the key here, so same-stage tasks with
    divergent trees or leaf shapes simply miss (they can no longer silently
    bind another stage's inputs)."""
    # lock-while-compiling witness (runtime/lockcheck.py, opt-in via
    # DFTPU_LOCK_CHECK=1): entering the XLA trace/compile/execute entry
    # point with an engine lock held stalls every contender for seconds —
    # the harness records it; no-op (one module-attr read) when off
    from datafusion_distributed_tpu.runtime import lockcheck as _lockcheck

    if _lockcheck.enabled():
        _lockcheck.note_blocking("xla_compile")

    task = task or DistributedTaskContext()
    tr = spans.current()
    traces_before = _TRACE_STATS["traces"]
    with tr.span("prepare", "prepare") as psp:
        prog = _prepare_program(
            plan, task, config, use_cache, shared_cache, shared_key, tr
        )
        psp.set(cache=prog.cache)
    gate = prog.first_call_gate
    # ends on the fetch of the flag vector: the sync the program already
    # makes, so the span holds the device's work and adds no wait
    def launch():
        # the jitted call up to its return: flatten, argument checks,
        # enqueue (and, a first call, trace and compile)
        with tr.span("launch", "launch"):
            return prog.fn(prog.inputs, prog.params)

    with tr.span("execute", "execute") as xsp:
        result = None
        if gate is not None and not gate["warmed"]:
            waited = tr.start_span("gate_wait", "wait")
            with gate["lock"]:
                tr.end_span(waited)
                # double-check: threads that queued behind the creator
                # must NOT execute under the gate (that would serialize
                # the whole task wave) — only the creator's
                # trace+compile+first-run is serialized; everyone else
                # re-checks and runs concurrently
                if not gate["warmed"]:
                    result = launch()
                    gate["warmed"] = True
        if result is None:
            result = launch()
        out, flags, metric_vals = result
        # the program's small outputs in ONE pull, the wait for the
        # device: the flag vector (both sentinel checks) and, for a
        # caller that keeps metrics, every traced metric value (every
        # copy is started before the first is waited on). The output's
        # row count is among them, the root's ``output_rows``; a trace
        # that recorded no metrics (``collect_metrics`` off) sends
        # ``out.num_rows`` along instead
        pulled = [flags]
        if metrics_store is not None:
            pulled += metric_vals
            names = prog.trace.metric_names
            if _ROOT_ROWS in names:
                rows_at = 1 + names.index(_ROOT_ROWS)
            else:
                rows_at = len(pulled)
                pulled.append(out.num_rows)
        with tr.span("sync", "sync", what="flags", values=len(pulled),
                     syncs=1):
            pulled = jax.device_get(pulled)
        flags = pulled[0]
        if tr.active:
            xsp.set(new_traces=_TRACE_STATS["traces"] - traces_before,
                    **prog.trace.counters)
    raise_flagged(prog.trace, "plan", check_overflow and flags[0], flags[1])
    if metrics_store is not None:
        # positions -> THIS submission's node ids (hoisting preserves the
        # original ids, so callers can look metrics up on their own plan)
        nodes = plan.collect(lambda _n: True)
        node_metrics: dict = {}
        for (pos, name), v in zip(prog.trace.metric_names, pulled[1:]):
            if 0 <= pos < len(nodes):
                node_metrics.setdefault(
                    nodes[pos].node_id, {})[name] = int(v)
        metrics_store.insert(task_label or f"task{task.task_index}",
                             node_metrics, rows_out=int(pulled[rows_at]))
    return out


@dataclass
class ProgramTrace:
    """What the trace of a plan leaves on the host (`trace_plan` fills
    it), kept beside the cached executable so that a program-cache hit,
    which runs no Python of the plan, still reads it."""

    #: labels of the nodes behind the capacity / precision flags, in the
    #: order `trace_plan` hands the flags back
    capacity_nodes: list = dc_field(default_factory=list)
    precision_nodes: list = dc_field(default_factory=list)
    #: (pre-order position, metric name) of every traced metric value
    metric_names: list = dc_field(default_factory=list)
    #: `ExecContext.counters` of the trace, for the executor's span
    counters: dict = dc_field(default_factory=dict)


def trace_plan(exec_target: ExecutionPlan, task: DistributedTaskContext,
               inputs: dict, config: dict, param_vecs,
               into: ProgramTrace):
    """Trace ``exec_target`` for one task inside a program being traced:
    the one place a program's root `ExecContext` is made. ``inputs``
    maps leaf node ids to that task's tables, ``param_vecs`` are the
    hoisted literals' vectors (None: the plan was not hoisted). Fills
    ``into``; -> (output table, capacity flags, precision flags, metric
    values), the lists traced scalars in ``into``'s order. How the flags
    are reduced and fetched is the calling executor's."""
    from datafusion_distributed_tpu.parallel.exchange import collective_tally
    from datafusion_distributed_tpu.plan.fingerprint import bound_params

    _TRACE_STATS["traces"] += 1
    ctx = ExecContext(task=task, inputs=inputs, config=config)
    # metric names and operator scopes are POSITION-addressed (pre-order
    # traversal index), not node-id-addressed: a fingerprint-shared
    # program executes for plan copies whose node ids differ from the
    # creator's, and fingerprint-equal trees traverse identically — the
    # caller remaps positions to ITS plan's node ids at insert time
    with traced_positions(exec_target) as pos_of, (
        contextlib.nullcontext() if param_vecs is None
        else bound_params(param_vecs)
    ), collective_tally() as carried:
        out = exec_target.execute(ctx)
    ctx.count("mesh_exchange_bytes", carried[0])
    into.capacity_nodes[:] = [label for label, _ in ctx.capacity_flags]
    into.precision_nodes[:] = [label for label, _ in ctx.precision_flags]
    into.metric_names[:] = [
        (pos_of.get(nid, -1), name) for nid, name, _ in ctx.metrics
    ]
    into.counters.clear()
    into.counters.update(ctx.counters)
    return (
        out,
        [f for _, f in ctx.capacity_flags],
        [f for _, f in ctx.precision_flags],
        [v for _, _, v in ctx.metrics],
    )


def any_flag(flags: list):
    """Traced OR of a list of traced bools (False for none)."""
    return jnp.any(jnp.stack(flags)) if flags else jnp.asarray(False)


# executor -> (capacity overflow, precision range) message. The texts
# are read by tests, by sql/context.py `_overflow_node_names` and on the
# far side of the worker wire.
_FLAG_TEXTS = {
    "plan": (
        "hash table overflow in plan (nodes: {}); re-plan with more slots",
        "int32 accumulator range exceeded in plan (nodes: {}); "
        "run with DFTPU_PRECISION=x64 for 64-bit accumulation",
    ),
    "mesh": (
        "exchange/hash capacity overflow on mesh (nodes: {}); "
        "re-plan with larger capacities",
        "int32 accumulator range exceeded on mesh (nodes: {}); "
        "run with DFTPU_PRECISION=x64 for 64-bit accumulation",
    ),
    "span": (
        "hash table overflow in span program (nodes: {}); "
        "re-plan with more slots",
        "int32 accumulator range exceeded in span program "
        "(nodes: {}); run with DFTPU_PRECISION=x64",
    ),
}


def raise_flagged(trace: ProgramTrace, where: str, capacity,
                  precision) -> None:
    """Raise what a program's fetched flags say, capacity first.
    ``capacity`` and ``precision`` are each one bool for all of the
    trace's nodes of that kind (the OR the program reduced on the
    device) or one bool a node; ``where`` is the executor, a key of
    `_FLAG_TEXTS`."""
    overflowed = _flagged(trace.capacity_nodes, capacity)
    out_of_range = _flagged(trace.precision_nodes, precision)
    if not (overflowed or out_of_range):
        return
    from datafusion_distributed_tpu.runtime.errors import (
        CapacityOverflowError,
        PrecisionRangeError,
    )

    cap_text, prec_text = _FLAG_TEXTS[where]
    if overflowed:
        raise CapacityOverflowError(cap_text.format(overflowed), overflowed)
    raise PrecisionRangeError(prec_text.format(out_of_range))


def _flagged(nodes: list, flags) -> list:
    flags = np.broadcast_to(np.asarray(flags, dtype=bool), (len(nodes),))
    return [n for n, f in zip(nodes, flags) if f]


@dataclass
class _Program:
    """What `_prepare_program` hands `execute_plan`."""

    fn: Callable  # the jitted (inputs, params) -> (out, flags, metrics)
    trace: ProgramTrace
    first_call_gate: Optional[dict]  # stage-shared programs only
    inputs: list
    params: tuple
    cache: str  # "hit" | "miss"


def _prepare_program(plan, task, config, use_cache, shared_cache,
                     shared_key, tr) -> _Program:
    """`execute_plan`'s host work before the device starts (its
    ``prepare`` span): hoist and fingerprint the plan, load the leaves,
    find or make the jitted program."""
    from datafusion_distributed_tpu.plan.fingerprint import prepare_plan

    # content-address the program: literal-hoisted plan + structural
    # fingerprint (None -> legacy object-identity keying). The hoisted
    # plan reuses the original's leaf objects, so leaf traversal order —
    # the positional input binding — is unchanged.
    prep = prepare_plan(plan)
    exec_target = prep.plan
    params = prep.param_arrays()
    leaves = collect_leaves(exec_target)
    # positional inputs, rebound to node ids INSIDE run via the closure
    # plan's own leaf order: node ids are minted per decode, so a shared
    # program traced from one task's plan copy must not see another copy's
    # ids in its input pytree — leaf traversal order is the cross-copy
    # stable identity (fingerprint-equal trees traverse identically)
    leaf_ids = [leaf.node_id for leaf in leaves if hasattr(leaf, "load")]
    with tr.span("h2d", "h2d") as hsp:
        input_list = [
            leaf.load(task) for leaf in leaves if hasattr(leaf, "load")
        ]
        if tr.active:
            # what an exchange left on the host crosses to the device
            # with the jitted call (inside ``execute``); its size is
            # known here, where the consumer's scans hand it over
            staged = [t for t in input_list if is_host_backed(t)]
            hsp.set(bytes=sum(spans.table_nbytes(t) for t in staged),
                    rows=sum(int(t.num_rows) for t in staged),
                    capacity=sum(t.capacity for t in staged))

    trace = ProgramTrace()

    def run(inp_list, param_vecs):
        out, cap_flags, prec_flags, metric_vals = trace_plan(
            exec_target, task, dict(zip(leaf_ids, inp_list)), config or {},
            param_vecs, trace,
        )
        # ONE packed flag vector: each separate scalar device->host fetch
        # is a synchronous round-trip, so both checks ride a single transfer
        flags = jnp.stack([any_flag(cap_flags), any_flag(prec_flags)])
        return out, flags, metric_vals

    # the distributed-tracing wire context (runtime/tracing.py
    # TRACE_CTX_KEY) must NEVER key a compiled program: its span ids
    # differ per task/query, so admitting it would force one XLA trace
    # per task. Worker.execute_task already strips it; this filter is the
    # defense for direct execute_plan callers.
    cfg_items = tuple(sorted(
        (k, v) for k, v in (config or {}).items() if k != "trace_ctx"
    ))
    # structural fingerprint -> content-addressed entry shared across plan
    # objects (fresh ctx.sql() submissions, literal-hoisted template
    # variants); no fingerprint -> legacy object-identity keying
    if prep.fingerprint is not None:
        cache_key = ("fp", prep.fingerprint, task.task_index,
                     task.task_count, cfg_items)
    else:
        cache_key = ("id", plan.node_id, task.task_index,
                     task.task_count, cfg_items)
    # the `ProgramTrace` must come from the SAME closure as the cached
    # executable, or cache hits would see it empty. use_cache=False
    # (worker path: per-task programs go through the TTL'd stage-share
    # cache instead) keeps one-shot programs out of the global cache so
    # their closures don't pin shipped task tables.
    cached = None  # (jitted run, its ProgramTrace)
    cache = "hit"
    if use_cache:
        with _CACHE_LOCK:
            cached = _COMPILE_CACHE.get(cache_key)
            if cached is not None:
                # move-to-end: LRU eviction must not take a live entry
                _COMPILE_CACHE.pop(cache_key)
                _COMPILE_CACHE[cache_key] = cached
    first_call_gate = None
    if cached is None and shared_cache is not None:
        # stage-shared program: key on the caller's stage identity, the
        # structural fingerprint (an order/identity mismatch between plan
        # copies now misses instead of silently binding wrong inputs), and
        # the input pytree structure + leaf shapes/dtypes (the only thing
        # that can legitimately differ between same-stage tasks)
        flat, treedef = jax.tree_util.tree_flatten(input_list)
        sig = tuple(
            (getattr(l, "shape", None), str(getattr(l, "dtype", type(l))))
            for l in flat
        )
        skey = (shared_key, prep.fingerprint, treedef, sig)
        # get-or-create under a lock: same-stage tasks fan out on coordinator
        # threads, and an unsynchronized check-then-act would have the first
        # wave all miss and compile duplicates — the exact cost this cache
        # removes. The creator also takes the entry's first-call gate so
        # concurrent siblings wait for its trace+compile instead of racing
        # jax's own dispatch into duplicate compiles.
        with _SHARED_LOCK:
            entry = shared_cache.get(skey)
            if entry is None:
                cache = "miss"
                _SHARED_STATS["miss"] += 1
                # entry cap: each entry's closure pins its creator task's
                # decoded plan (incl. device tables) until the query slot's
                # TTL/LRU turnover — a wide stage whose keys fragment
                # (per-task dictionary identity, remainder shapes) must not
                # retain one plan per task. Insertion-order eviction; an
                # evicted program just recompiles on next use.
                while len(shared_cache) >= _SHARED_ENTRY_CAP:
                    shared_cache.pop(next(iter(shared_cache)))
                entry = (
                    jax.jit(run), trace,
                    {"lock": threading.Lock(), "warmed": False},
                )
                shared_cache[skey] = entry
            else:
                _SHARED_STATS["hit"] += 1
        cached, first_call_gate = entry[:2], entry[2]
    if cached is None:
        cache = "miss"
        cached = (jax.jit(run), trace)
        if use_cache:
            with _CACHE_LOCK:
                # bounded LRU eviction (was: a full clear() at the cap — a
                # cliff that recompiled EVERY live query at once)
                while len(_COMPILE_CACHE) >= _COMPILE_CACHE_MAX:
                    _COMPILE_CACHE.pop(next(iter(_COMPILE_CACHE)))
                _COMPILE_CACHE[cache_key] = cached
    return _Program(*cached, first_call_gate, input_list, params, cache)


_COMPILE_CACHE: dict = {}  # insertion order == LRU order (move-to-end on hit)
_CACHE_LOCK = threading.Lock()
# stage-shared program cache observability: hits = task executions that
# reused another task's traced program (each hit ~= one XLA compile avoided)
_SHARED_STATS = {"hit": 0, "miss": 0}
_SHARED_LOCK = threading.Lock()
_SHARED_ENTRY_CAP = 32  # per-query distinct (stage, shape-class) programs


def _plan_cache_default() -> int:
    import os as _os

    try:
        return max(int(_os.environ.get("DFTPU_PLAN_CACHE", "512")), 1)
    except ValueError:
        return 512


_COMPILE_CACHE_MAX = _plan_cache_default()


def set_plan_cache_size(n) -> None:
    """Resize the compiled-program LRU (SET distributed.plan_cache_size /
    DFTPU_PLAN_CACHE). Shrinking evicts oldest entries immediately."""
    global _COMPILE_CACHE_MAX
    _COMPILE_CACHE_MAX = max(int(n), 1)
    with _CACHE_LOCK:
        while len(_COMPILE_CACHE) > _COMPILE_CACHE_MAX:
            _COMPILE_CACHE.pop(next(iter(_COMPILE_CACHE)))


# program-trace counter: incremented once per traced program body (the
# 1:1 proxy for XLA compiles — cache hits never re-run the traced python).
# The recompile-regression tests assert on deltas of this counter.
_TRACE_STATS = {"traces": 0}


def trace_count() -> int:
    return _TRACE_STATS["traces"]


def _dicts_of(table: Table) -> dict:
    return {
        n: c.dictionary
        for n, c in zip(table.names, table.columns)
        if c.dictionary is not None
    }
