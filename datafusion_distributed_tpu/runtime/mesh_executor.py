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
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

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

    # host phase: load every task's slice of every leaf, stack to [T, ...].
    # POSITIONAL (leaf traversal order), not node-id keyed: node ids are
    # minted per plan object, and a dict keyed on them would change the
    # input pytree structure between fingerprint-equal plan copies.
    leaf_ids = [leaf.node_id for leaf in leaves if hasattr(leaf, "load")]
    stacked_inputs: list[Table] = []
    tr = tracing.current()
    traces_before = trace_count()
    with tr.span("mesh.stack_inputs", "mesh.stack_inputs") as ssp:
        for leaf in leaves:
            if not hasattr(leaf, "load"):
                continue
            per_task = [
                leaf.load(DistributedTaskContext(i, num_tasks))
                for i in range(num_tasks)
            ]
            stacked_inputs.append(
                jax.tree.map(lambda *xs: jnp.stack(xs), *per_task)
            )
        if tr.active:
            # every task's slice of every leaf, stacked on the first
            # device before `shard_map` re-places it
            ssp.set(bytes=sum(tracing.table_nbytes(t)
                              for t in stacked_inputs),
                    tasks=num_tasks)

    trace = ProgramTrace()

    def axis_any(flags):
        return jax.lax.pmax(any_flag(flags).astype(jnp.int32), AXIS) > 0

    def run(inputs_stacked, param_vecs):
        # local view: leading task axis of size 1 -> squeeze
        local_inputs = {
            nid: jax.tree.map(lambda x: x[0], t)
            for nid, t in zip(leaf_ids, inputs_stacked)
        }
        out, cap_flags, prec_flags, metric_vals = trace_plan(
            exec_target, DistributedTaskContext(0, num_tasks), local_inputs,
            {"mesh_axis": AXIS, "num_tasks": num_tasks}, param_vecs, trace,
        )
        if metric_vals:
            mvec = jnp.stack(
                [v.astype(_METRIC_DTYPE) for v in metric_vals]
            )[None, :]
        else:
            mvec = jnp.zeros((1, 0), dtype=_METRIC_DTYPE)
        return out, axis_any(cap_flags), axis_any(prec_flags), mvec

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
                 tuple(d.id for d in mesh.devices.flat))
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
                out_specs=(P(), P(), P(), P(AXIS)),
                check_rep=False,
            )
        )
        cached = (fn, trace)
        _MESH_COMPILE_CACHE[cache_key] = cached
    fn, trace = cached
    # ends on the fetch of the two flags, the sync this path already makes
    with tr.span("mesh.execute", "mesh.execute",
                 cache="hit" if cached_hit else "miss") as xsp:
        out, any_overflow, any_precision, mvec = fn(stacked_inputs, params)
        any_overflow = check_overflow and bool(any_overflow)
        any_precision = bool(any_precision)
        if tr.active:
            xsp.set(new_traces=trace_count() - traces_before,
                    **trace.counters)
    raise_flagged(trace, "mesh", any_overflow, any_precision)
    if metrics_store is not None:
        nodes = plan.collect(lambda _n: True)
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
