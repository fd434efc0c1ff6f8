"""Meshes-as-workers: a Worker that owns a device mesh and runs a SPAN of
a stage's tasks as ONE SPMD program.

This composes the two tiers of SURVEY.md §2.10 ("same-mesh = collective,
off-mesh = host RPC") that previously never met: the host coordinator/worker
runtime (exchanges between workers) and the mesh executor (SPMD over a
device mesh). A stage with T tasks running over K mesh workers of width W
is dispatched as contiguous spans — worker k executes tasks
[kW, (k+1)W) by stacking the span's leaf slices over its mesh axis and
shard_mapping the stage pipeline: one XLA program per worker per stage
instead of W host-scheduled programs, with data staying in that mesh's
HBM. Between meshes the existing host planes (peer pulls / coordinator
streams) move bytes per-task, unchanged — the reference's whole L3+L7
topology (`/root/reference/src/worker/worker_service.rs:42-52`) with the
intra-worker parallelism swapped from a thread pool to a device mesh.

Stage plans contain no exchange nodes (exchanges end stages), so the
span program has no collectives — its parallelism is pure data-parallel
SPMD; any stray exchange raises loudly (no mesh_axis in the exec config).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from datafusion_distributed_tpu.ops.table import Table, concat_tables
from datafusion_distributed_tpu.plan.exchanges import IsolatedArmExec
from datafusion_distributed_tpu.plan.physical import (
    DistributedTaskContext,
    ExecutionPlan,
    MemoryScanExec,
    ParquetScanExec,
    ProgramTrace,
    raise_flagged,
    trace_plan,
)
from datafusion_distributed_tpu.runtime.mesh_executor import (
    place_task_tables,
)
from datafusion_distributed_tpu.runtime.worker import (
    TaskData,
    TaskKey,
    Worker,
)

AXIS = "span"

# same import as mesh_executor.py: the experimental entry point still
# accepts check_rep (the top-level jax.shard_map dropped it)
from jax.experimental.shard_map import shard_map as _shard_map


def span_specializable(plan: ExecutionPlan) -> bool:
    """Span dispatch covers the regular stage shapes; plans whose leaves
    depend on the GLOBAL task index in ways a local re-slice cannot express
    (isolated union arms, work-unit feeds) fall back to per-task dispatch."""
    from datafusion_distributed_tpu.runtime.work_unit_feed import (
        WorkUnitScanExec,
    )

    return not plan.collect(
        lambda n: isinstance(n, (IsolatedArmExec, WorkUnitScanExec))
    )


def span_specialized(plan: ExecutionPlan, lo: int, hi: int) -> ExecutionPlan:
    """Re-slice a stage plan's leaves to tasks [lo, hi), re-indexed to
    local positions 0..hi-lo (the mesh axis): the span analogue of
    `_task_specialized` (`query_coordinator.rs:346-382`)."""
    from datafusion_distributed_tpu.runtime.peer import PeerShuffleScanExec

    def walk(node: ExecutionPlan) -> ExecutionPlan:
        if isinstance(node, PeerShuffleScanExec):
            if node.pinned_task is not None or node.pull_all:
                return node
            if node.replicated:
                # broadcast: wrap virtual-partition ids so a span wider
                # than the planned fan-out still pulls a FULL copy per
                # local task (an out-of-range local index would read an
                # empty build side and silently drop join matches)
                P_ = max(node.num_partitions, 1)
                pulls = [
                    node.pulls_per_task[(lo + i) % P_]
                    for i in range(hi - lo)
                ]
            else:
                pulls = node.pulls_per_task[lo:hi]
            return PeerShuffleScanExec(
                pulls, node.key_names, node.num_partitions,
                node.per_dest_capacity, node._schema, node.dictionaries,
                replicated=node.replicated, budget_bytes=node.budget_bytes,
                chunk_rows=node.chunk_rows,
                capacity_hint=node.capacity_hint,
            )
        if isinstance(node, MemoryScanExec) and not node.pinned and (
            not node.replicated
        ):
            sub = node.tasks[lo:hi]
            if not sub and node.tasks:
                # span entirely past this scan's slices (sibling feeds had
                # more): per-task dispatch would read empty via the
                # tasks[0] reference; give the span the same empty table
                from datafusion_distributed_tpu.plan.physical import (
                    _dicts_of,
                )

                ref = node.tasks[0]
                sub = [Table.empty(node.schema(), ref.capacity,
                                   _dicts_of(ref))]
            return MemoryScanExec(sub, node.schema())
        if isinstance(node, ParquetScanExec):
            return ParquetScanExec(
                node.file_groups[lo:hi], node.schema(), node.capacity,
                projection=node.projection, dictionaries=node.dictionaries,
            )
        children = [walk(c) for c in node.children()]
        return node.with_new_children(children) if children else node

    return walk(plan)


def execute_stage_span_on_mesh(
    plan: ExecutionPlan,
    mesh: Mesh,
    span_width: int,
    task_count: int,
    config: Optional[dict] = None,
) -> list[Table]:
    """Execute a span-specialized stage plan over ``mesh``: local task i of
    the span runs on device i; -> per-task output Tables. No collectives —
    out_specs stack the per-device outputs on the span axis.

    Compilation is NOT memoized across calls (unlike mesh_executor's
    _MESH_COMPILE_CACHE): every span plan arrives freshly decoded with new
    node ids AND query-specific leaves (peer pull keys carry the query id,
    memscan refs are per-shipment uuids), so a cache key would virtually
    never repeat; each span also executes exactly once per query. If a
    workload emerges that re-ships byte-identical span plans, key a cache
    on (plan_obj JSON hash, mesh devices, input shape/dict signature) at
    set_stage_plan and reuse the decoded plan object so jit's own cache
    hits."""
    # each task's slice goes straight to its device of THIS mesh
    # (`place_task_tables`): an input pulled from another mesh arrives
    # committed to foreign devices (the in-process bypass shares buffers),
    # a host-backed one (the zero-copy plane's peer pulls arrive as numpy
    # views) enters the device exactly once
    stacked = {
        leaf.node_id: place_task_tables(
            _repad_uniform([
                leaf.load(DistributedTaskContext(i, task_count))
                for i in range(span_width)
            ]), mesh)
        for leaf in plan.collect(lambda n: not n.children())
        if hasattr(leaf, "load")
    }

    trace = ProgramTrace()

    def run(inputs_stacked):
        local = {
            nid: jax.tree.map(lambda x: x[0], t)
            for nid, t in inputs_stacked.items()
        }
        # the plan was not hoisted: no parameter vectors
        out, cap_flags, prec_flags, _ = trace_plan(
            plan, DistributedTaskContext(0, task_count), local,
            dict(config or {}), None, trace,
        )
        # one row of flags a task, capacity flags first
        flags = cap_flags + prec_flags
        flags = jnp.stack(flags) if flags else jnp.zeros((0,), jnp.bool_)
        return (
            jax.tree.map(lambda x: x[None], out),
            flags[None, :],
        )

    in_specs = jax.tree.map(lambda _: P(AXIS), stacked)
    fn = _shard_map(
        run, mesh=mesh, in_specs=(in_specs,),
        out_specs=(P(AXIS), P(AXIS)), check_rep=False,
    )
    # multi-device executables cache fine (see the serialization note in
    # mesh_executor.py — the old disable-around-invocation workaround was
    # removed after re-verification)
    out_stacked, flags = jax.jit(fn)(stacked)
    # [W, F] -> which node's flag any task of the span raised
    flagged = np.asarray(flags).any(axis=0)
    n_cap = len(trace.capacity_nodes)
    raise_flagged(trace, "span", flagged[:n_cap], flagged[n_cap:])
    return [
        jax.tree.map(lambda x: x[i], out_stacked) for i in range(span_width)
    ]


def _repad_uniform(tables: list[Table]) -> list[Table]:
    """Stacking requires identical shapes AND identical pytree structure/
    aux across the span's slices: same capacity (peer pulls concat to
    exact row counts, so capacities routinely differ by a few chunks),
    same Dictionary identity per string column (pulled slices carry their
    producers' dictionaries; empty fallbacks may carry none), and same
    validity presence."""
    from datafusion_distributed_tpu.ops.table import (
        Column,
        unify_dictionaries,
    )

    cap = max(int(t.capacity) for t in tables)
    tables = [
        t if int(t.capacity) == cap else concat_tables([t], capacity=cap)
        for t in tables
    ]
    names = tables[0].names
    ncols = len(names)
    new_cols: list[list] = [[None] * ncols for _ in tables]
    for ci in range(ncols):
        cols = [t.columns[ci] for t in tables]
        d, luts = unify_dictionaries([c.dictionary for c in cols])
        has_validity = any(c.validity is not None for c in cols)
        for ti, c in enumerate(cols):
            data = c.data
            lut = luts[ti]
            if lut is not None:
                if len(lut) == 0:
                    data = jnp.zeros_like(data)
                else:
                    data = jnp.asarray(lut)[
                        jnp.clip(data, 0, len(lut) - 1)
                    ]
            validity = c.validity
            if has_validity and validity is None:
                validity = jnp.ones(data.shape, dtype=jnp.bool_)
            new_cols[ti][ci] = Column(
                data, validity, c.dtype,
                d if d is not None else c.dictionary,
            )
    return [
        Table(names, tuple(new_cols[ti]), tables[ti].num_rows)
        for ti in range(len(tables))
    ]


@dataclass
class _SpanState:
    """Shared state of one shipped span: the plan runs ONCE on the mesh;
    every task key of the span serves its slot from the cached outputs."""

    plan: ExecutionPlan
    lo: int
    hi: int
    task_count: int
    outputs: Optional[list] = None
    lock: threading.Lock = field(default_factory=threading.Lock)
    config: dict = field(default_factory=dict)


class MeshWorker(Worker):
    """A Worker whose executor is a device mesh: spans of stage tasks run
    as one SPMD program (`execute_stage_span_on_mesh`); the per-task
    service surface (execute_task / partition streams / peer pulls) is
    inherited unchanged — consumers cannot tell a mesh worker from a
    thread-pool worker."""

    def __init__(self, url: str, devices, ttl_seconds: float = 600.0,
                 version: str = "0.1.0", peer_channels=None):
        super().__init__(url, ttl_seconds, version,
                         peer_channels=peer_channels)
        self.devices = list(devices)
        self.mesh = Mesh(np.asarray(self.devices), (AXIS,))
        self.mesh_width = len(self.devices)
        self._spans: dict = {}  # (query_id, stage_id, lo) -> _SpanState; per-query: bounded 16

    # -- control plane ------------------------------------------------------
    def set_stage_plan(self, query_id: str, stage_id: int, lo: int, hi: int,
                       task_count: int, plan_obj: dict,
                       config: Optional[dict] = None,
                       headers: Optional[dict] = None,
                       ttl: Optional[float] = None) -> None:
        """Ship ONE span-specialized plan covering tasks [lo, hi); registers
        a TaskData per task so the inherited data-plane surfaces work."""
        from datafusion_distributed_tpu.runtime.codec import (
            collect_table_ids,
            decode_plan,
        )
        from datafusion_distributed_tpu.runtime.errors import (
            wrap_worker_exception,
        )
        from datafusion_distributed_tpu.runtime.peer import (
            attach_peer_channels,
        )

        key0 = TaskKey(query_id, stage_id, lo)
        try:
            plan = decode_plan(plan_obj, self.table_store)
            # same post-decode integrity/verify gate as Worker.set_plan:
            # span programs are stage-shared BY CONSTRUCTION, so a
            # mis-decoded span plan is exactly the wrong-binding hazard
            from datafusion_distributed_tpu.runtime.worker import (
                _check_decoded_plan,
            )

            _check_decoded_plan(plan, plan_obj, self.url, key0,
                                config=config)
            if self.on_plan is not None:
                plan = self.on_plan(plan, key0)
        except Exception as e:
            raise wrap_worker_exception(e, self.url, key0) from e
        attach_peer_channels(plan, self.peer_channels, self)
        state = _SpanState(plan=plan, lo=lo, hi=hi, task_count=task_count,
                           config=dict(config or {}))
        # bounded retention: span outputs are device buffers; a long-lived
        # worker must not accumulate them past the active-query window
        # (task-level cleanup still runs through the registry as usual)
        while len(self._spans) >= 16:
            self._spans.pop(next(iter(self._spans)))
        self._spans[(query_id, stage_id, lo)] = state
        tids = collect_table_ids(plan_obj)
        for i in range(lo, hi):
            data = TaskData(
                key=TaskKey(query_id, stage_id, i), plan=plan,
                task_count=task_count, config=dict(config or {}),
                headers=dict(headers or {}),
                shipped_table_ids=tids if i == lo else [],
                ttl=ttl,
            )
            data.span = (state, i - lo)  # type: ignore[attr-defined]
            self.registry.put(data)

    # -- data plane ---------------------------------------------------------
    def execute_task(self, key: TaskKey) -> Table:
        data = self.registry.get(key)
        span = getattr(data, "span", None) if data is not None else None
        if span is None:
            return super().execute_task(key)
        state, local_idx = span
        import time as _time

        with state.lock:
            if state.outputs is None:
                data.executed_at = _time.time()
                # always run at full mesh width: a short span's trailing
                # devices load empty slices (the reference's short
                # coalesce groups yield empty streams the same way)
                state.outputs = execute_stage_span_on_mesh(
                    state.plan, self.mesh, self.mesh_width,
                    state.task_count, config=state.config,
                )
                data.finished_at = _time.time()
        out = state.outputs[local_idx]
        data.metrics.setdefault("rows_out", int(out.num_rows))
        data.metrics.setdefault("span", [state.lo, state.hi])
        return out


class InMemoryMeshCluster:
    """K mesh workers × W devices each over the process's device list —
    the meshes-as-workers test fixture: 2×4 on the virtual 8-device CPU
    mesh models two hosts each owning a 4-chip slice, with the host data
    plane (peer pulls) between them."""

    def __init__(self, num_workers: int, devices_per_worker: int,
                 devices=None, ttl_seconds: float = 600.0):
        devices = list(devices if devices is not None else jax.devices())
        need = num_workers * devices_per_worker
        if len(devices) < need:
            raise ValueError(
                f"{need} devices needed, {len(devices)} available"
            )
        self.workers = {}
        for k in range(num_workers):
            url = f"mesh://worker-{k}"
            self.workers[url] = MeshWorker(
                url,
                devices[k * devices_per_worker:(k + 1) * devices_per_worker],
                ttl_seconds=ttl_seconds,
            )
        for w in self.workers.values():
            w.peer_channels = self

    def get_urls(self):
        return list(self.workers.keys())

    def get_worker(self, url: str):
        return self.workers[url]
