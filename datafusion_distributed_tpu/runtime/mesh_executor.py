"""Mesh executor: run a staged plan as ONE SPMD program over a device mesh.

The reference's execution runtime is a coordinator fanning tasks to workers
over gRPC and streaming batches back (SURVEY.md §3.2). Inside a TPU mesh the
whole thing collapses: every stage's tasks are the mesh's devices, exchanges
are collectives, and the *entire multi-stage query* jits into a single
`shard_map`ped XLA program — planning/fusion/overlap handled by the compiler,
data never leaving HBM/ICI. (Cross-mesh / multi-host coordination lives in
runtime/coordinator.py, which shells out to this executor per mesh.)
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from datafusion_distributed_tpu import precision
from datafusion_distributed_tpu.ops.table import Table
from datafusion_distributed_tpu.plan.physical import (
    DistributedTaskContext,
    ExecutionPlan,
    ProgramTrace,
    any_flag,
    raise_flagged,
    trace_count,
    trace_plan,
)
from datafusion_distributed_tpu.runtime import tracing

# per-task metric counters (row/byte counts); 32-bit in tpu precision mode
_METRIC_DTYPE = precision.ACC_INT

AXIS = "tasks"

# The four-device executable goes through jax's persistent compile cache
# like any single-device one. On the v5e (PR 29, TPC-H q1 at SF1) a fresh
# process compiled and wrote it in a 28.2 s warm-up, the next process
# loaded it in 9.0 s, and every result of both agreed with the oracle.
# Only tests/conftest.py skips multi-device cache WRITES, for the aged
# suite processes of the CPU backend.

# Re-executing the SAME plan object on the same mesh reuses the compiled
# SPMD program (the reference's cached TaskData plan re-execution analogue).
# Small LRU: entries are whole compiled multi-stage SPMD executables (tens
# to hundreds of MB each on the CPU backend) and are only ever reused for
# the SAME plan object — across different queries they are dead weight.
# A 99-query sweep in one process accumulated >100 GB before the OOM
# killer took it at the old cap of 256. Workloads that ALTERNATE among
# more than the cap's worth of memoized plans (dashboard refresh loops)
# can raise DFTPU_MESH_CACHE to trade memory for recompiles.
_MESH_COMPILE_CACHE: dict = {}
# clamped to >= 1: a zero/negative cap would make the eviction loop pop from
# an empty dict on the first compile (the cache cannot be disabled, only
# minimized — every execution needs its own entry live while running)
_MESH_COMPILE_CACHE_CAP = max(int(os.environ.get("DFTPU_MESH_CACHE", "8")), 1)


def make_mesh(num_tasks: Optional[int] = None, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = num_tasks or len(devices)
    if n > len(devices):
        # devices[:n] would silently build a narrower mesh: a 4-task query
        # on a one-chip host must say so, not run on one device
        raise ValueError(
            f"mesh of {n} tasks requested but only {len(devices)} "
            "device(s) are available"
        )
    return Mesh(np.asarray(devices[:n]), (AXIS,))


def place_task_tables(per_task, mesh: Mesh) -> Table:
    """``per_task[i]`` on the mesh's device i, as ONE Table whose buffers
    are ``[tasks, ...]`` arrays laid out as a `shard_map` with ``in_specs``
    of ``P(axis)`` asks: shard i is task i's buffer under a leading axis of
    one, copied from where it lives straight to device i (a host buffer
    enters the device there, once). Nothing is stacked on one device, and
    `jit` has nothing to move before the program starts. The tables share
    their shapes and their pytree structure."""
    devices = list(mesh.devices.flat)
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))

    def place(*buffers):
        shards = [
            jax.device_put(
                # a row count may be a Python int (`partition_table`)
                (b if hasattr(b, "ndim") else np.int32(b))[None], device
            )
            for b, device in zip(buffers, devices)
        ]
        return jax.make_array_from_single_device_arrays(
            (len(devices),) + shards[0].shape[1:], sharding, shards
        )

    return jax.tree.map(place, *per_task)


def _lies_on(buffer, device) -> bool:
    """Whether ``buffer`` is a device array held by ``device`` alone."""
    return getattr(buffer, "devices", lambda: None)() == {device}


def place_partitions(per_task, mesh: Mesh) -> tuple[Table, int]:
    """A partitioned table's partitions (`MemoryScanExec.partitioned`) as
    the shards of ONE Table on ``mesh``, where they lie: each column a flat
    ``[tasks x capacity]`` array laid out as ``P(axis)`` asks, whose shard i
    IS task i's buffer, and the row counts a ``[tasks]`` array. A buffer
    already on its task's device is neither copied nor stacked under a
    leading axis (`place_task_tables` makes a ``[1, capacity]`` copy); one
    elsewhere (a task past the partitions reads an empty table) is copied
    there. -> (the Table, the bytes copied)."""
    devices = list(mesh.devices.flat)
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    copied = [0]

    def shard(buffer, device):
        if _lies_on(buffer, device):
            return buffer
        copied[0] += int(buffer.nbytes)
        return jax.device_put(buffer, device)

    def place(*buffers):
        shards = [shard(b, d) for b, d in zip(buffers, devices)]
        return jax.make_array_from_single_device_arrays(
            (len(devices) * shards[0].shape[0],), sharding, shards)

    columns = jax.tree.map(place, *[t.columns for t in per_task])
    rows = jax.make_array_from_single_device_arrays(
        (len(devices),), sharding,
        [jax.device_put(np.asarray(t.num_rows, np.int32).reshape(1), d)
         for t, d in zip(per_task, devices)])
    return Table(per_task[0].names, columns, rows), copied[0]


@dataclass(frozen=True)
class _Placement:
    """A leaf's inputs on a mesh's chips, kept on the leaf: it lives as
    long as the cached plan does, and no longer."""

    devices: tuple  # the mesh's devices, in task order
    # what `load` returned, task by task. Held, not `id`s: an object that
    # is alive cannot hand its identity to another.
    sources: tuple
    # `place_partitions(sources, mesh)` where ``resident``, else
    # `place_task_tables(sources, mesh)`
    table: Table
    # the leaf's partitions are the shards, where they lie: flat
    # ``[tasks x capacity]`` buffers, not ``[tasks, capacity]``
    resident: bool
    copied: int  # the bytes the placement put on the chips


_PLACEMENT_ATTR = "_dftpu_mesh_placement"


def _placed_on_mesh(leaf, mesh: Mesh) -> tuple[_Placement, bool]:
    """-> (the leaf's input on ``mesh``, whether an earlier request placed
    it). A placement is reused while the leaf's `load` hands back the SAME
    Table objects (tables are immutable; a cached plan's `MemoryScanExec`
    does, a `ParquetScanExec` or a scan of fresh exchange output does not)
    for the same devices; anything else is placed anew, and the old
    placement dropped first. A partitioned scan whose every partition
    lies on its task's device is used where it lies (`place_partitions`);
    any other leaf's tasks are copied to their chips
    (`place_task_tables`)."""
    num_tasks = mesh.shape[AXIS]
    sources = tuple(
        leaf.load(DistributedTaskContext(i, num_tasks))
        for i in range(num_tasks)
    )
    devices = tuple(mesh.devices.flat)
    held = getattr(leaf, _PLACEMENT_ATTR, None)
    if (held is not None and held.devices == devices
            and all(a is b for a, b in zip(held.sources, sources))):
        return held, True
    setattr(leaf, _PLACEMENT_ATTR, None)
    resident = getattr(leaf, "partitioned", False) and all(
        _lies_on(column.data, device)
        for source, device in zip(sources[:len(leaf.tasks)], devices)
        for column in source.columns)
    if resident:
        table, copied = place_partitions(sources, mesh)
    else:
        table = place_task_tables(sources, mesh)
        copied = tracing.table_nbytes(table)
    held = _Placement(devices, sources, table, resident, copied)
    setattr(leaf, _PLACEMENT_ATTR, held)
    return held, False


def execute_on_mesh(
    plan: ExecutionPlan,
    mesh: Mesh,
    check_overflow: bool = True,
    metrics_store=None,
) -> Table:
    """Execute a distributed plan (root output replicated) on a mesh.

    With ``metrics_store`` (runtime/metrics.py protocol), traced per-node
    metrics come back per task via a P(axis)-stacked program output and are
    inserted under labels task0..taskN-1."""
    from datafusion_distributed_tpu.plan.fingerprint import prepare_plan

    num_tasks = mesh.shape[AXIS]
    # content-address the SPMD program: fingerprint-equal plans (fresh
    # submissions, literal-hoisted variants) reuse the compiled executable
    prep = prepare_plan(plan)
    exec_target = prep.plan
    params = prep.param_arrays()
    leaves = exec_target.collect(lambda n: not n.children())

    # host phase: every task's slice of every leaf on that task's chip,
    # placed once and kept on the leaf (`_placed_on_mesh`).
    # POSITIONAL (leaf traversal order), not node-id keyed: node ids are
    # minted per plan object, and a dict keyed on them would change the
    # input pytree structure between fingerprint-equal plan copies.
    scans = [leaf for leaf in leaves if hasattr(leaf, "load")]
    leaf_ids = [leaf.node_id for leaf in scans]
    tr = tracing.current()
    traces_before = trace_count()
    with tr.span("mesh.stack_inputs", "mesh.stack_inputs") as ssp:
        placed = [_placed_on_mesh(leaf, mesh) for leaf in scans]
        stacked_inputs = [held.table for held, _ in placed]
        resident = tuple(held.resident for held, _ in placed)
        if tr.active:
            # what THIS request put on the chips: 0 bytes where every
            # leaf's placement was reused, or lay where it is used
            ssp.set(bytes=sum(held.copied for held, reused in placed
                              if not reused),
                    tasks=num_tasks,
                    reused=sum(reused for _, reused in placed))

    trace = ProgramTrace()

    def axis_any(flags):
        return jax.lax.pmax(any_flag(flags).astype(jnp.int32), AXIS) > 0

    # the plan a (re)trace reads is the CALLER's, handed over for the
    # length of its call: an entry of the compile cache outlives the plan
    # that made it (fingerprint-equal plans share it), and must not pin
    # that plan's leaves, their task slices and their placement
    calling = threading.local()

    def run(inputs_stacked, param_vecs):
        target, target_leaf_ids, leaf_resident = calling.plan
        # local view: a stacked leaf's leading task axis of size 1 ->
        # squeeze; a resident one's buffers are the partition's own, and
        # its row count a [1]
        local_inputs = {
            nid: (Table(t.names, t.columns, t.num_rows[0]) if flat
                  else jax.tree.map(lambda x: x[0], t))
            for nid, t, flat in zip(target_leaf_ids, inputs_stacked,
                                    leaf_resident)
        }
        out, cap_flags, prec_flags, metric_vals = trace_plan(
            target, DistributedTaskContext(0, num_tasks), local_inputs,
            {"mesh_axis": AXIS, "num_tasks": num_tasks}, param_vecs, trace,
        )
        # known here, where their inputs arrive as the partitions, and
        # kept with the program as every counter is
        trace.counters["partitioned_scans"] = sum(leaf_resident)
        if metric_vals:
            mvec = jnp.stack(
                [v.astype(_METRIC_DTYPE) for v in metric_vals]
            )[None, :]
        else:
            mvec = jnp.zeros((1, 0), dtype=_METRIC_DTYPE)
        # ONE flag vector, as `execute_plan` packs it: each scalar pulled
        # alone is a round trip of its own
        flags = jnp.stack([axis_any(cap_flags), axis_any(prec_flags)])
        return out, flags, mvec

    # pytree-PREFIX specs (one spec per leaf Table / param vector, applied
    # to the whole subtree): a full spec tree would bake the creator's
    # pytree aux (dictionary identities) into the cached executable and
    # fail structure matching when a fingerprint-equal plan copy carries
    # fresh Dictionary objects — prefix specs make that a plain retrace
    in_specs = [P(AXIS)] * len(stacked_inputs)
    param_specs = (P(), P())  # replicated
    # fingerprint -> shared across fresh submissions / hoisted variants;
    # unfingerprintable plans fall back to object identity as before
    cache_key = (prep.fingerprint or ("id", plan.node_id),
                 tuple(d.id for d in mesh.devices.flat), resident)
    cached = _MESH_COMPILE_CACHE.get(cache_key)
    cached_hit = cached is not None
    if cached is not None:
        # move-to-end: LRU eviction must not take the entry being reused
        _MESH_COMPILE_CACHE.pop(cache_key)
        _MESH_COMPILE_CACHE[cache_key] = cached
    if cached is None:
        while len(_MESH_COMPILE_CACHE) >= _MESH_COMPILE_CACHE_CAP:
            _MESH_COMPILE_CACHE.pop(next(iter(_MESH_COMPILE_CACHE)))
        fn = jax.jit(
            shard_map(
                run,
                mesh=mesh,
                in_specs=(in_specs, param_specs),
                out_specs=(P(), P(), P(AXIS)),
                check_rep=False,
            )
        )
        cached = (fn, trace, calling)
        _MESH_COMPILE_CACHE[cache_key] = cached
    fn, trace, calling = cached
    # ends on the fetch of the flags, the sync this path already makes
    with tr.span("mesh.execute", "mesh.execute",
                 cache="hit" if cached_hit else "miss") as xsp:
        calling.plan = (exec_target, leaf_ids, resident)
        try:
            with tr.span("launch", "launch"):
                out, flags, mvec = fn(stacked_inputs, params)
        finally:
            del calling.plan
        # one fetch for both checks: the wait for the chips
        with tr.span("sync", "sync", what="flags", values=1, syncs=1):
            flags = np.asarray(flags)
        if tr.active:
            xsp.set(new_traces=trace_count() - traces_before,
                    **trace.counters)
    raise_flagged(trace, "mesh", check_overflow and flags[0], flags[1])
    if metrics_store is not None:
        nodes = plan.collect(lambda _n: True)
        with tr.span("sync", "sync", what="metrics",
                     values=int(mvec.size), syncs=1):
            m = np.asarray(mvec)  # [T, M]
        for t in range(m.shape[0]):
            node_metrics: dict = {}
            for (pos, name), v in zip(trace.metric_names, m[t]):
                if 0 <= pos < len(nodes):
                    node_metrics.setdefault(
                        nodes[pos].node_id, {}
                    )[name] = int(v)
            metrics_store.insert(f"task{t}", node_metrics)
    return out
