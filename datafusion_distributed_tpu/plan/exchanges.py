"""Exchange operators: the stage-boundary nodes of the distributed plan.

These are the TPU-native counterparts of the reference's three
`NetworkBoundary` implementations (`/root/reference/src/execution_plans/`):

    ShuffleExchangeExec   <- NetworkShuffleExec   (hash N:M re-shard)
    CoalesceExchangeExec  <- NetworkCoalesceExec  (N -> 1 concat)
    BroadcastExchangeExec <- NetworkBroadcastExec (replicate to all)

A boundary splits the plan into stages (producer below, consumer above).
Under the mesh executor the whole staged tree traces into one SPMD program —
`execute` simply emits the collective. The boundary duality of the reference
(Pending/Ready; `network_shuffle.rs` Stage::Local vs Stage::Remote) shows up
here as: the same node can run in-mesh (collective) or across meshes via the
host runtime (runtime/), which materializes producer output and re-feeds
consumers — that path is the DCN/multi-host fallback.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from datafusion_distributed_tpu.ops.table import Column, Table, round_up_pow2
from datafusion_distributed_tpu.parallel.exchange import (
    broadcast_exchange,
    coalesce_exchange,
    group_coalesce_exchange,
    shuffle_exchange,
)
from datafusion_distributed_tpu.plan.physical import ExecContext, ExecutionPlan


class ExchangeExec(ExecutionPlan):
    """Common base: a stage boundary with a producer child."""

    is_exchange = True

    def __init__(self, child: ExecutionPlan, num_tasks: int):
        super().__init__()
        self.child = child
        self.num_tasks = num_tasks
        # stamped by the prepare pass (stage ids mirror the reference's
        # (query_id, stage_num) TaskKey addressing)
        self.stage_id: Optional[int] = None
        # producer-stage task count when it differs from the consumer side
        # (stamped by the task-count lattice; None = uniform num_tasks).
        # Coalesce's num_tasks already IS the producer count.
        self.producer_tasks: Optional[int] = None
        # downstream LIMIT's fetch+skip (stamped by the planner's limit
        # rule): the streaming data plane stops pulling producer chunks
        # once this many rows arrived (host tier only; in-mesh collectives
        # are single-program and already bounded by the local limit)
        self.consumer_fetch: Optional[int] = None
        # planner-predicted bytes crossing this boundary (stamped by the
        # partial-aggregate push-down from sampled NDV statistics; the
        # coordinator records predicted-vs-measured through the telemetry
        # registry). Never a compile-cache or fingerprint input — it
        # annotates the plan, it does not shape the trace.
        self.predicted_exchange_bytes: Optional[int] = None

    def children(self):
        return [self.child]

    def schema(self):
        return self.child.schema()

    def execute(self, ctx: ExecContext):
        """Memoized: an exchange's collective runs exactly once per traced
        program (see ExecContext.exchange_cache)."""
        cached = ctx.exchange_cache.get(self.node_id)
        if cached is not None:
            return cached
        out = super().execute(ctx)
        ctx.exchange_cache[self.node_id] = out
        return out

    def _require_axis(self, ctx: ExecContext) -> str:
        axis = ctx.config.get("mesh_axis")
        if axis is None:
            raise RuntimeError(
                f"{type(self).__name__} executed outside a mesh; use the "
                "distributed executor (runtime/) or a shard_map context"
            )
        return axis


class ShuffleExchangeExec(ExchangeExec):
    """Hash shuffle: rows re-shard across tasks by key hash."""

    def __init__(
        self,
        child: ExecutionPlan,
        key_names: Sequence[str],
        num_tasks: int,
        per_dest_capacity: int,
    ):
        super().__init__(child, num_tasks)
        self.key_names = list(key_names)
        # sizing policy lives in planner/distributed.py _mk_shuffle (driven
        # by DistributedConfig.shuffle_skew_factor and the overflow retry)
        self.per_dest_capacity = per_dest_capacity

    def with_new_children(self, children):
        n = ShuffleExchangeExec(
            children[0], self.key_names, self.num_tasks, self.per_dest_capacity
        )
        n.stage_id = self.stage_id
        n.producer_tasks = self.producer_tasks
        n.consumer_fetch = self.consumer_fetch
        n.predicted_exchange_bytes = self.predicted_exchange_bytes
        return n

    def output_capacity(self):
        # a consumer task receives <= per_dest_capacity from EACH producer
        # task (mesh tier: producers == the axis width == num_tasks)
        t_prod = (self.producer_tasks if self.producer_tasks is not None
                  else self.num_tasks)
        return t_prod * self.per_dest_capacity

    def _execute(self, ctx: ExecContext) -> Table:
        t = self.child.execute(ctx)
        out, overflow = shuffle_exchange(
            t, self.key_names, self._require_axis(ctx), self.num_tasks,
            self.per_dest_capacity,
        )
        ctx.record_overflow(self, overflow)
        return out

    def display(self):
        return (
            f"ShuffleExchange keys=[{', '.join(self.key_names)}] "
            f"tasks={self.num_tasks} per_dest_cap={self.per_dest_capacity}"
        )


class RangeShuffleExchangeExec(ExchangeExec):
    """Range shuffle on a composite SORT key (distributed sample sort):
    after this exchange, task i's rows all order before task i+1's, so a
    LOCAL sort per task followed by an order-preserving coalesce yields
    the global sort order. Replaces the coalesce-then-global-sort plan for
    unlimited ORDER BY: the old shape made every device gather and re-sort
    the full T*C dataset; this one sorts T-way in parallel and never
    re-sorts after the gather. (The reference leans on single-node
    SortPreservingMergeExec above a coalesce, `inject_network_boundaries.rs`
    sort case — a merge is the streaming-CPU analogue of the same idea.)
    """

    def __init__(
        self,
        child: ExecutionPlan,
        sort_keys,  # list[ops.sort.SortKey]
        num_tasks: int,
        per_dest_capacity: int,
    ):
        super().__init__(child, num_tasks)
        self.sort_keys = list(sort_keys)
        self.per_dest_capacity = per_dest_capacity

    def with_new_children(self, children):
        n = RangeShuffleExchangeExec(
            children[0], self.sort_keys, self.num_tasks,
            self.per_dest_capacity,
        )
        n.stage_id = self.stage_id
        n.producer_tasks = self.producer_tasks
        n.consumer_fetch = self.consumer_fetch
        return n

    def output_capacity(self):
        t_prod = (self.producer_tasks if self.producer_tasks is not None
                  else self.num_tasks)
        return t_prod * self.per_dest_capacity

    def _execute(self, ctx: ExecContext) -> Table:
        from datafusion_distributed_tpu.parallel.exchange import (
            range_shuffle_exchange,
        )

        t = self.child.execute(ctx)
        out, overflow = range_shuffle_exchange(
            t, self.sort_keys, self._require_axis(ctx), self.num_tasks,
            self.per_dest_capacity,
        )
        ctx.record_overflow(self, overflow)
        return out

    def display(self):
        keys = ", ".join(
            f"{k.name}{'' if k.ascending else ' DESC'}" for k in self.sort_keys
        )
        return (
            f"RangeShuffleExchange keys=[{keys}] tasks={self.num_tasks} "
            f"per_dest_cap={self.per_dest_capacity}"
        )


class PartitionReplicatedExec(ExchangeExec):
    """REPLICATED -> PARTITIONED: every task keeps the row-index slice
    ``row % num_tasks == task`` of its (identical) copy. No communication —
    the inverse of a broadcast, used when a replicated subtree feeds a
    partition-wise consumer (e.g. a UNION arm)."""

    def with_new_children(self, children):
        n = PartitionReplicatedExec(children[0], self.num_tasks)
        n.stage_id = self.stage_id
        n.producer_tasks = self.producer_tasks
        n.consumer_fetch = self.consumer_fetch
        return n

    def output_capacity(self):
        return self.child.output_capacity()

    def _execute(self, ctx: ExecContext) -> Table:
        import jax

        t = self.child.execute(ctx)
        axis = self._require_axis(ctx)
        me = jax.lax.axis_index(axis)
        idx = jnp.arange(t.capacity, dtype=jnp.int32)
        keep = t.row_mask() & ((idx % self.num_tasks) == me)
        return t.compact(keep)

    def display(self):
        return f"PartitionReplicated tasks={self.num_tasks}"


class CoalesceExchangeExec(ExchangeExec):
    """Producer tasks' rows coalesced for the consumer stage.

    ``num_consumers == 1`` (default): gathered into one logical table,
    replicated on every task (the consumer stage is the SPMD root).
    ``num_consumers = M > 1``: true N:M — consumer task j holds the
    contiguous producer group [j*g, (j+1)*g), g = div_ceil(N, M) (the
    reference's `network_coalesce.rs` arithmetic); memory per task is
    g*C instead of N*C."""

    def __init__(self, child: ExecutionPlan, num_tasks: int,
                 num_consumers: int = 1):
        super().__init__(child, num_tasks)
        self.num_consumers = num_consumers

    def with_new_children(self, children):
        n = CoalesceExchangeExec(
            children[0], self.num_tasks, self.num_consumers
        )
        n.stage_id = self.stage_id
        n.producer_tasks = self.producer_tasks
        n.consumer_fetch = self.consumer_fetch
        return n

    def output_capacity(self):
        if self.num_consumers > 1:
            g = -(-self.num_tasks // self.num_consumers)
            return self.child.output_capacity() * g
        return self.child.output_capacity() * self.num_tasks

    def _execute(self, ctx: ExecContext) -> Table:
        t = self.child.execute(ctx)
        axis = self._require_axis(ctx)
        if self.num_consumers > 1:
            return group_coalesce_exchange(
                t, axis, self.num_tasks, self.num_consumers
            )
        return coalesce_exchange(t, axis, self.num_tasks)

    def display(self):
        m = (f" consumers={self.num_consumers}"
             if self.num_consumers > 1 else "")
        return f"CoalesceExchange tasks={self.num_tasks}{m}"


class IsolatedArmExec(ExecutionPlan):
    """One UNION arm assigned to a single task — the TPU-native analogue of
    the reference's ChildrenIsolatorUnionExec child->task assignment
    (`children_isolator_union.rs:39-100`). A replicated arm would otherwise
    be computed identically on EVERY task and deduplicated after the fact
    (x T wasted compute); isolation computes it exactly once:

    - mesh tier: `lax.cond(axis_index == assigned, run_arm, empty)` — SPMD
      control flow diverges per device, the arm's FLOPs execute on one chip
      (arms contain no collectives by construction: exchanges end stages)
    - host tier: task specialization ships the arm only to its assigned
      worker (other tasks get an empty scan), mirroring the reference's
      task-specialized plan stripping (`query_coordinator.rs:346-382`)
    """

    def __init__(self, child: ExecutionPlan, assigned_task: int):
        super().__init__()
        self.child = child
        self.assigned_task = assigned_task

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return IsolatedArmExec(children[0], self.assigned_task)

    def schema(self):
        return self.child.schema()

    def output_capacity(self):
        return self.child.output_capacity()

    def _execute(self, ctx: ExecContext) -> Table:
        import jax

        axis = ctx.config.get("mesh_axis")
        if axis is None:
            # host tier: static task index (specialization usually removed
            # this node already; this is the in-process fallback)
            if ctx.task.task_count > 1 and (
                ctx.task.task_index != self.assigned_task
            ):
                return self._empty_like(ctx)
            return self.child.execute(ctx)
        me = jax.lax.axis_index(axis)

        from datafusion_distributed_tpu.ops.table import (
            pin_dictionary_caches,
        )

        with pin_dictionary_caches():
            return self._execute_mesh_arm(ctx, me)

    def _execute_mesh_arm(self, ctx: ExecContext, me) -> Table:
        """Probe + lax.cond traces, with the dictionary memo caches pinned
        for the duration: both traces must observe the SAME Dictionary
        objects or their pytree metadata diverges (ops/table.py)."""
        import jax

        # Exchanges inside the arm contain COLLECTIVES, which every task
        # must execute unconditionally (a collective inside one lax.cond
        # branch deadlocks/aborts). Pre-execute them into the shared cache
        # with the REAL context (their overflow flags propagate normally);
        # the conditioned part is then only the arm's post-exchange local
        # compute — which is exactly the duplicated-work segment isolation
        # exists to eliminate.
        for ex in self.child.collect(
            lambda n: getattr(n, "is_exchange", False)
        ):
            ex.execute(ctx)

        # Probe the arm under a throwaway child context (sharing the
        # exchange cache): its outputs are used for SHAPES/DTYPES only, so
        # XLA dead-code-eliminates the probe's compute; what it recorded
        # tells us the side-channel structure the cond branches must
        # return explicitly (tracers may not escape a branch via the
        # context). An arm's trace-time counters stay uncounted.
        probe_ctx = ctx.child()
        probe = self.child.execute(probe_ctx)

        def run_arm(_):
            arm_ctx = ctx.child()
            return self.child.execute(arm_ctx), arm_ctx.traced_values()

        def empty_arm(_):
            cols = tuple(
                Column(
                    jnp.zeros(c.data.shape, c.data.dtype),
                    jnp.zeros(c.validity.shape, jnp.bool_)
                    if c.validity is not None else None,
                    c.dtype,
                    c.dictionary,
                )
                for c in probe.columns
            )
            t = Table(probe.names, cols, jnp.zeros((), dtype=jnp.int32))
            return t, tuple(
                jnp.zeros((), v.dtype) for v in probe_ctx.traced_values()
            )

        out, values = jax.lax.cond(
            me == self.assigned_task, run_arm, empty_arm, None
        )
        ctx.adopt(probe_ctx, values)
        return out

    def _empty_like(self, ctx: ExecContext) -> Table:
        t = self.child.execute(ctx.child())
        return Table(t.names, t.columns, jnp.zeros((), dtype=jnp.int32))

    def display(self):
        return f"IsolatedArm task={self.assigned_task}"


def assign_arms_to_tasks(weights: Sequence[float], num_tasks: int) -> list:
    """Weighted child->task assignment (greedy LPT): heaviest arm first to
    the least-loaded task. Covers the reference's tasks <, =, > children
    cases (`children_isolator_union.rs:39-83`): with fewer arms than tasks
    some tasks receive none; with more, tasks receive several."""
    loads = [0.0] * num_tasks
    assignment = [0] * len(weights)
    for i in sorted(range(len(weights)), key=lambda i: -weights[i]):
        task = min(range(num_tasks), key=lambda t: loads[t])
        assignment[i] = task
        loads[task] += weights[i]
    return assignment


class BroadcastExchangeExec(ExchangeExec):
    """Replicate rows to every task (broadcast-join build sides)."""

    def with_new_children(self, children):
        n = BroadcastExchangeExec(children[0], self.num_tasks)
        n.stage_id = self.stage_id
        n.producer_tasks = self.producer_tasks
        n.consumer_fetch = self.consumer_fetch
        return n

    def output_capacity(self):
        return self.child.output_capacity() * self.num_tasks

    def _execute(self, ctx: ExecContext) -> Table:
        t = self.child.execute(ctx)
        return broadcast_exchange(t, self._require_axis(ctx), self.num_tasks)

    def display(self):
        return f"BroadcastExchange tasks={self.num_tasks}"
