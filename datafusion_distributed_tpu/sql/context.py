"""SessionContext: the user-facing API (the DataFusion `SessionContext`
analogue the reference extends via `DistributedExt`,
`/root/reference/src/distributed_ext.rs`).

    ctx = SessionContext()
    ctx.register_parquet("lineitem", "lineitem.parquet")
    df = ctx.sql("select l_returnflag, sum(l_quantity) from lineitem group by 1")
    df.collect()        # -> pyarrow Table
    df.to_pandas()
    df.explain()

Tables are decoded to padded device Tables at registration (host Parquet
decode happens once; every query then runs device-side). String dictionaries
are unified per table column at load.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Sequence

from datafusion_distributed_tpu.io.parquet import (
    Partitions,
    arrow_to_partitions,
    arrow_to_table,
    schema_from_arrow,
    table_to_arrow,
)
from datafusion_distributed_tpu.ops.table import PartitionedTable, Table
from datafusion_distributed_tpu.plan.physical import (
    ExecutionPlan,
    MemoryScanExec,
    execute_plan,
    refuse_partitioned_scans,
)
from datafusion_distributed_tpu.runtime import tracing
from datafusion_distributed_tpu.schema import Schema
from datafusion_distributed_tpu.sql import parser as ast
from datafusion_distributed_tpu.sql.logical import Binder, LogicalPlan
from datafusion_distributed_tpu.sql.parser import (
    CreateView,
    DropView,
    ExplainVerify,
    SetOption,
    parse_statements,
)
from datafusion_distributed_tpu.sql.planner import PhysicalPlanner, PlannerConfig


#: distinct sentinel for Catalog._ndv_cache misses (None is a valid
#: cached verdict: "no such column")
_NDV_MISS = object()


class Catalog:
    """Named tables (device-resident) + views. NDV computation and
    registration serialize on a lock: the serving tier plans concurrent
    submissions from N client threads against one catalog. A table is a
    `Table` on the default device or a `PartitionedTable`, a partition a
    device; every question below is answered for the whole table."""

    def __init__(self) -> None:
        import threading

        self.tables: dict[str, Table | PartitionedTable] = {}
        self.views: dict[str, LogicalPlan] = {}
        self._ndv_cache: dict = {}
        self._ndv_lock = threading.Lock()
        # bumped on every (re-)registration: physical plans embed scan
        # Tables and plan-time scalar-subquery results, so the session's
        # plan cache keys on this to drop plans built over replaced data
        self.generation = 0

    def register_table(self, name: str,
                       table: Table | PartitionedTable) -> None:
        with self._ndv_lock:
            self.tables[name.lower()] = table
            self.generation += 1
            self._ndv_cache = {
                k: v for k, v in self._ndv_cache.items()
                if k[0] != name.lower()
            }

    def has_table(self, name: str) -> bool:
        return name.lower() in self.tables

    def table_schema(self, name: str) -> Schema:
        return self.tables[name.lower()].schema()

    def table_rows(self, name: str) -> int:
        return int(self.tables[name.lower()].num_rows)

    def column_ndv(self, table: str, column: str):
        """Exact distinct count, computed once per column (drives the join
        orderer's fan-out estimates — the statistics the reference gets from
        DataFusion's table providers)."""
        key = (table.lower(), column)
        with self._ndv_lock:
            cached = self._ndv_cache.get(key, _NDV_MISS)
            gen0 = self.generation
        if cached is not _NDV_MISS:
            return cached
        import numpy as np

        t = self.tables.get(table.lower())
        if t is None or column not in t:
            ndv = None
        else:
            # sample-bounded: the heuristic only needs the order of
            # magnitude, and a full 60M-row device->host pull at bind
            # time would eat the benchmark budget. STRIDED, not a prefix:
            # generated keys are clustered (l_orderkey repeats ~4x in a
            # run), so a prefix under-counts distincts and freezes the
            # estimate below the extrapolation threshold.
            # a partitioned table is sampled at one stride over all of
            # its partitions, in their order
            parts, rows = ((t.parts, t.rows) if isinstance(
                t, PartitionedTable) else ((t,), (int(t.num_rows),)))
            total = sum(rows)
            n = min(total, 1 << 20)
            stride = max(1, total // max(n, 1))
            cols = [part.column(column) for part in parts]
            vals = np.concatenate([
                np.asarray(col.data[:r:stride])
                for col, r in zip(cols, rows)])[:n]
            if cols[0].validity is not None:
                vals = vals[np.concatenate([
                    np.asarray(col.validity[:r:stride])
                    for col, r in zip(cols, rows)])[:n]]
            sampled = max(len(vals), 1)
            ndv = int(len(np.unique(vals)))
            # distinct-on-sample extrapolates only when near-unique
            # (a saturated sample means the column's true NDV is small)
            if ndv > 0.9 * sampled:
                ndv = min(int(ndv * (total / sampled)), total)
            elif sampled < total:
                # a non-extrapolated sampled count can still undercount
                # the true NDV; pad it so downstream hash-table sizing
                # (which treats this as an upper bound) overflows less
                ndv = min(int(ndv * 1.5) + 16, total)
        # the compute ran OUTSIDE the lock (concurrent planners may race
        # the same cold column; both compute the same deterministic
        # value). Cache only if the catalog generation is unchanged — a
        # re-registration mid-compute means this estimate sampled the
        # REPLACED table and must not be installed for the new one.
        with self._ndv_lock:
            if self.generation != gen0:
                return ndv
            return self._ndv_cache.setdefault(key, ndv)

    def column_domain(self, table: str, column: str):
        """The codes a dictionary column's rows can hold: ``len`` of its
        dictionary, one more where it has a validity array (NULL is a key
        of its own; `ops/aggregate.py _dictionary_bases`). None for a
        column without a dictionary."""
        t = self.tables.get(table.lower())
        if t is None or column not in t:
            return None
        col = t.column(column)
        if col.dictionary is None:
            return None
        return len(col.dictionary) + (col.validity is not None)

    def scan_exec(self, name: str, columns: Sequence[str]) -> ExecutionPlan:
        t = self.tables[name.lower()]
        if isinstance(t, PartitionedTable):
            # a task a partition, each read where it lies: the mesh tier
            # alone runs such a scan (`MemoryScanExec.partitioned`)
            return MemoryScanExec([p.select(columns) for p in t.parts],
                                  t.schema().select(columns),
                                  partitioned=True)
        return MemoryScanExec([t.select(columns)], t.schema().select(columns))


@dataclass
class SessionConfig:
    planner: PlannerConfig = None  # type: ignore[assignment]
    overflow_retries: int = 3
    # `SET distributed.<key> = <value>` overrides, applied when building the
    # DistributedConfig (the reference's ConfigExtension with prefix
    # "distributed"; coordinator->worker propagation rides the plan codec).
    # Keys that are not DistributedConfig fields flow verbatim into
    # Coordinator.config_options — that is how the runtime knobs travel:
    # the data-plane ones (peer_shuffle, stream_chunk_rows,
    # worker_connection_buffer_budget_bytes, ...) and the fault-tolerance
    # layer's (max_task_retries, task_retry_backoff_s, task_timeout_s,
    # dispatch_timeout_s, quarantine_threshold, quarantine_seconds — see
    # runtime/coordinator.py FAULT_TOLERANCE_DEFAULTS).
    distributed_options: dict = None  # type: ignore[assignment]
    # user headers forwarded verbatim to workers (auth etc.; the
    # passthrough_headers analogue)
    passthrough_headers: dict = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.planner is None:
            self.planner = PlannerConfig()
        if self.distributed_options is None:
            self.distributed_options = {}
        if self.passthrough_headers is None:
            self.passthrough_headers = {}

    def set_option(self, name: str, value) -> None:
        scope, _, key = name.partition(".")
        if scope == "distributed":
            # compiled-program cache knobs apply process-wide (the caches
            # are module-level); they also stay in distributed_options so
            # EXPLAIN-style introspection and workers see the setting
            if key == "plan_cache_size":
                from datafusion_distributed_tpu.plan.physical import (
                    set_plan_cache_size,
                )

                set_plan_cache_size(int(value))
            elif key == "literal_hoisting":
                from datafusion_distributed_tpu.plan.fingerprint import (
                    set_literal_hoisting,
                )

                set_literal_hoisting(value)
            elif key == "verify_plans":
                from datafusion_distributed_tpu.plan.verify import MODES

                value = str(value).strip().lower()
                if value not in MODES:
                    raise ValueError(
                        f"invalid verify_plans mode {value!r} (expected "
                        f"one of {MODES})"
                    )
            elif key == "data_plane":
                # cross-process data-plane selection (runtime/
                # coordinator.py _data_plane): auto keeps the routing
                # ladder; unary/stream/shm force one plane. Execution
                # routing only — NEVER trace-relevant (toggling planes
                # must recompile nothing; the byte-identity gates in
                # tests/test_shm_plane.py pin that)
                value = str(value).strip().lower()
                if value not in ("auto", "unary", "stream", "shm"):
                    raise ValueError(
                        f"invalid data_plane {value!r} (expected one of "
                        f"('auto', 'unary', 'stream', 'shm'))"
                    )
            elif key == "wire_compression":
                # transfer-RPC wire codec policy: auto = adaptive
                # per-column choice (runtime/codec.py), zstd/lz4 force a
                # codec (still downgraded through per-connection
                # negotiation when an end can't decode it), off ships
                # raw frames
                value = str(value).strip().lower()
                if value not in ("auto", "off", "zstd", "lz4"):
                    raise ValueError(
                        f"invalid wire_compression {value!r} (expected "
                        f"one of ('auto', 'off', 'zstd', 'lz4'))"
                    )
            elif key == "max_concurrent_queries":
                # serving-tier admission knobs (runtime/serving.py) are
                # validated at SET time: a bad value must fail the SET,
                # not wedge admission decisions mid-serve
                value = int(value)
                if value < 1:
                    raise ValueError(
                        "max_concurrent_queries must be >= 1"
                    )
            elif key == "admission_budget_bytes":
                value = float(value)
                if value < 0:
                    raise ValueError(
                        "admission_budget_bytes must be >= 0 (0 = "
                        "unlimited)"
                    )
            elif key == "worker_memory_budget_bytes":
                # enforced per-worker staging budget (runtime/codec.py
                # TableStore + runtime/spill.py): validated at SET time
                # like the admission knobs; 0 = unlimited. Deliberately
                # NOT trace-relevant — flipping it never recompiles.
                value = float(value)
                if value < 0:
                    raise ValueError(
                        "worker_memory_budget_bytes must be >= 0 (0 = "
                        "unlimited)"
                    )
            elif key == "worker_memory_redline":
                # red-line shedding factor (runtime/serving.py): resident
                # bytes over budget x factor preempt the lowest-priority
                # running query; 0 disables shedding
                value = float(value)
                if value != 0 and value < 1.0:
                    raise ValueError(
                        "worker_memory_redline must be 0 (shedding off) "
                        "or >= 1.0 (a red-line below the budget would "
                        "shed before spill/backpressure even engage)"
                    )
            elif key == "checkpoint_budget_bytes":
                # CheckpointStore byte cap (runtime/checkpoint.py):
                # oldest recoverable checkpoints evict past it
                value = float(value)
                if value < 0:
                    raise ValueError(
                        "checkpoint_budget_bytes must be >= 0 (0 = "
                        "uncapped)"
                    )
            elif key == "result_cache_budget_bytes":
                # ResultCache byte budget (runtime/result_cache.py):
                # cold entries past it SPILL (SpillManager) instead of
                # evicting, and refault byte-exactly on the next hit
                value = float(value)
                if value < 0:
                    raise ValueError(
                        "result_cache_budget_bytes must be >= 0 (0 = "
                        "unlimited)"
                    )
            elif key == "serving_stage_slots":
                value = int(value)
                if value < 0:
                    raise ValueError(
                        "serving_stage_slots must be >= 0 (0 = auto: "
                        "the worker count)"
                    )
            elif key in ("fair_share", "zero_copy", "hedging",
                         "checkpointing", "pipelined_shuffle",
                         "partial_agg_pushdown", "result_cache"):
                # boolean knobs: fair_share (serving scheduler policy),
                # zero_copy (view-based data plane — `off` restores the
                # copying plane everywhere), hedging (straggler
                # speculative re-dispatch), checkpointing (query
                # checkpoint/resume), pipelined_shuffle (streaming
                # first-slice shuffle boundaries — `off` restores the
                # materialized plane), partial_agg_pushdown (statistics-
                # driven pre-exchange partial aggregation), result_cache
                # (fingerprint-keyed whole-result + sub-plan reuse —
                # runtime/result_cache.py). One shared parser so
                # SET-time coercion and runtime reads can't drift.
                from datafusion_distributed_tpu.ops.table import (
                    parse_bool_knob,
                )

                value = parse_bool_knob(value)
            elif key == "hedge_quantile":
                # hedging knobs validated at SET time like the serving
                # admission knobs: a bad value must fail the SET, not
                # silently disable (or stampede) the hedger mid-serve
                value = float(value)
                if not 0.0 <= value <= 1.0:
                    raise ValueError("hedge_quantile must be in [0, 1]")
            elif key == "hedge_floor_s":
                value = float(value)
                if value < 0:
                    raise ValueError("hedge_floor_s must be >= 0")
            elif key == "hedge_budget":
                value = int(value)
                if value < 0:
                    raise ValueError(
                        "hedge_budget must be >= 0 (0 disables hedging "
                        "by denying every speculative attempt)"
                    )
            elif key == "slo_p99_ms":
                # SLO targets (runtime/telemetry.py SloTracker, read
                # live by the serving tier's stats/console surfaces):
                # validated at SET time like the other serving knobs
                value = float(value)
                if value <= 0:
                    raise ValueError("slo_p99_ms must be > 0")
            elif key == "slo_error_rate":
                value = float(value)
                if not 0.0 <= value <= 1.0:
                    raise ValueError(
                        "slo_error_rate must be in [0, 1]"
                    )
            elif key == "tracing":
                # distributed-tracing mode (runtime/tracing.py):
                # validated at SET time so a typo fails the SET, not the
                # queries silently running untraced
                from datafusion_distributed_tpu.runtime.tracing import (
                    TRACING_MODES,
                )

                value = str(value).strip().lower()
                if value not in TRACING_MODES:
                    raise ValueError(
                        f"invalid tracing mode {value!r} (expected one "
                        f"of {TRACING_MODES})"
                    )
            elif key == "tracing_sample_rate":
                value = float(value)
                if not 0.0 <= value <= 1.0:
                    raise ValueError(
                        "tracing_sample_rate must be in [0, 1]"
                    )
            elif key == "skew_split_factor":
                # runtime-adaptivity knobs (runtime/adaptivity.py):
                # validated at SET time like the serving knobs, and
                # deliberately NOT trace-relevant — flipping any of them
                # recompiles nothing (pinned in test_recompile_budget.py)
                value = float(value)
                if value != 0 and value < 1.0:
                    raise ValueError(
                        "skew_split_factor must be 0 (splitting off) or "
                        ">= 1.0 (a hot partition is one ABOVE the "
                        "median)"
                    )
            elif key == "skew_split_min_rows":
                value = int(value)
                if value < 0:
                    raise ValueError(
                        "skew_split_min_rows must be >= 0"
                    )
            elif key == "partial_agg_bailout_ratio":
                value = float(value)
                if not 0.0 <= value <= 1.0:
                    raise ValueError(
                        "partial_agg_bailout_ratio must be in [0, 1] "
                        "(0 disables the bail-out; 1 bails only when "
                        "the partial reduces nothing)"
                    )
            elif key == "replan_cardinality_factor":
                value = float(value)
                if value != 0 and value < 1.0:
                    raise ValueError(
                        "replan_cardinality_factor must be 0 (replan "
                        "off) or >= 1.0 (measured/estimated divergence "
                        "factor)"
                    )
            self.distributed_options[key] = value
        elif scope == "planner":
            if not hasattr(self.planner, key):
                raise ValueError(f"unknown planner option {key!r}")
            setattr(self.planner, key, value)
        else:
            raise ValueError(f"unknown option scope {scope!r}")

    def distributed_snapshot(self) -> dict:
        """GIL-atomic copy of `distributed_options`: under the serving
        tier a client thread's first `SET distributed.<new_key>` can
        insert a key while another query's driver copies the dict, and a
        Python-level `dict(d)`/`.items()` iteration racing that insert
        raises "dictionary changed size during iteration" — failing an
        innocent query. `list(d.items())` materializes in one C call
        (no bytecode runs mid-snapshot), so readers always see a
        consistent point-in-time copy."""
        return dict(list(self.distributed_options.items()))


class OverflowRetryAbandoned(RuntimeError):
    """Raised (instead of another widening) when an overflow retry's plan
    would exceed the device-memory budget."""


def _overflow_node_names(err) -> str:
    """The capacity-overflow errors embed the failing program's capacity-
    capable node labels ("... (nodes: ['HashAggregate']); ..."). The flag is
    OR-reduced on device (one device->host fetch), so the individual culprit
    is unknown — but the candidate SET is, and it bounds which planner knobs
    a retry must widen."""
    import re as _re

    m = _re.search(r"nodes: \[([^\]]*)\]", str(err))
    return m.group(1) if m else ""


def _widen_for_overflow(pcfg: "PlannerConfig", dcfg, err,
                        force_all: bool = False):
    """-> (pcfg, dcfg) with only the capacity knobs implicated by the
    overflow error widened 4x. ``dcfg`` is None for single-process collects
    (no shuffle capacities exist there).

    A global widening compounds across knobs: an undersized aggregate table
    in one stage of q2 (SF0.5, adaptive tier) 4x'd join expansion AND
    shuffle skew query-wide, and two retries planned ~916GB of device
    buffers — tripping the byte-budget guard and failing a query a targeted
    agg widening converges in one retry. If NO knob applicable to the given
    configs is implicated (unparseable list, a future node class's label,
    or shuffle-only with dcfg=None), everything applicable widens: the
    alternative is re-executing the byte-identical plan every retry.

    ``force_all`` (the retry loops pass it on the LAST widening) also
    widens everything: targeting serializes knob discovery — an agg that
    needs two widenings hides a shuffle overflow behind it — so the final
    attempt must not die one knob short of the old global behavior."""
    names = _overflow_node_names(err)
    join = "Join" in names
    agg = "Aggregate" in names
    shuf = "Shuffle" in names and dcfg is not None
    if force_all or not (join or agg or shuf):
        join = agg = True
        shuf = dcfg is not None
    pcfg = replace(
        pcfg,
        join_expansion_factor=pcfg.join_expansion_factor * (4 if join else 1),
        agg_slot_factor=pcfg.agg_slot_factor * (4 if agg else 1),
    )
    if shuf:
        dcfg = replace(dcfg, shuffle_skew_factor=dcfg.shuffle_skew_factor * 4)
    return pcfg, dcfg


def _overflow_retry_guard(plan, attempt: int, last_err) -> None:
    """Abandon an overflow retry whose widened plan would need more device
    memory than the budget (DFTPU_RETRY_BYTES_BUDGET, default 16 GB):
    capacity factors compound 4x per retry, and dispatching a ~100GB plan
    fails with an opaque allocator error (or the OOM killer) instead of
    the overflow error the caller can reason about."""
    if attempt == 0:
        return
    import os as _os

    from datafusion_distributed_tpu.planner.statistics import (
        plan_device_bytes,
    )

    raw = _os.environ.get("DFTPU_RETRY_BYTES_BUDGET", "")
    try:
        budget = float(raw) if raw else 16e9
    except ValueError:
        raise RuntimeError(
            f"DFTPU_RETRY_BYTES_BUDGET={raw!r} is not a number"
        ) from None
    need = plan_device_bytes(plan)
    if need > budget:
        raise OverflowRetryAbandoned(
            f"overflow-retry abandoned: widened plan needs ~{need/1e9:.1f}GB "
            f"device buffers (budget {budget/1e9:.1f}GB, "
            "DFTPU_RETRY_BYTES_BUDGET); original overflow: "
            f"{last_err}"
        )


class DataFrame:
    """A planned (but unexecuted) query."""

    def __init__(self, ctx: "SessionContext", logical: LogicalPlan,
                 request_id: Optional[str] = None):
        self.ctx = ctx
        self.logical = logical
        # the request's identifier: the root of every trace begun for
        # this DataFrame carries it (runtime/tracing.py `layer_report`
        # merges them). Minted by the first call that is traced (None
        # until then). Traces are begun and finished inside one call, so
        # a DataFrame that is never collected pins nothing in the store.
        self.request_id = request_id
        # plan memoization: repeated collect() of the same DataFrame reuses
        # the plan object. Lookups go through the SESSION-level cache keyed
        # by the logical plan's structural fingerprint, so a fresh
        # ctx.sql(same_text) from a distinct submission reuses the planned
        # physical tree too (plan/fingerprint.py); this dict is the
        # fallback for logical plans without a fingerprint.
        self._plan_cache: dict = {}
        self._logical_fp = -1  # lazily computed; None = unfingerprintable

    def _logical_fingerprint(self):
        if self._logical_fp == -1:
            from datafusion_distributed_tpu.plan.fingerprint import (
                logical_fingerprint,
            )

            self._logical_fp = logical_fingerprint(self.logical)
        return self._logical_fp

    def _plan_cache_get(self, key):
        lfp = self._logical_fingerprint()
        if lfp is None:
            return self._plan_cache.get(key)
        return self.ctx._plan_cache_get(
            (lfp, self.ctx.catalog.generation) + key
        )

    def _plan_cache_put(self, key, plan) -> None:
        lfp = self._logical_fingerprint()
        if lfp is None:
            self._plan_cache[key] = plan
        else:
            self.ctx._plan_cache_put(
                (lfp, self.ctx.catalog.generation) + key, plan
            )

    @staticmethod
    def _pcfg_key(cfg: PlannerConfig) -> tuple:
        """EVERY PlannerConfig field keys the plan caches (same rule as the
        DistributedConfig cfg_key below: a hand-picked subset silently
        serves stale plans when e.g. max_slots changes via SET — at
        session-cache scope a fresh ctx.sql() no longer re-plans, so the
        key must carry the full config)."""
        return tuple(
            getattr(cfg, k) for k in type(cfg).__dataclass_fields__
        )

    def physical_plan(self, config: Optional[PlannerConfig] = None,
                      subquery_executor=None) -> ExecutionPlan:
        from datafusion_distributed_tpu.plan.verify import (
            enforce_verification,
        )

        cfg = config or self.ctx.config.planner
        key = ("single", self._pcfg_key(cfg), subquery_executor is not None)
        plan = self._plan_cache_get(key)
        if plan is None:
            planner = PhysicalPlanner(self.ctx.catalog, cfg, subquery_executor)
            plan = planner.plan(self.logical)
            self._plan_cache_put(key, plan)
        # static verification at the cheapest point — before any trace/
        # compile (plan/verify.py; memoized on the plan object, so cache
        # hits and retry-loop re-submissions re-verify for free)
        enforce_verification(
            plan, options=self.ctx.config.distributed_options,
            context="physical plan",
        )
        return plan

    def collect_table(self) -> Table:
        """Execute, with automatic re-plan on hash/join capacity overflow —
        the static-shape analogue of the reference's pending->ready two-phase
        planning: capacities are planned optimistically and revised on
        overflow."""
        with self._trace_call("query") as call:
            def attempt(n, pcfg, _dcfg, guard):
                with call.tracer.span("attempt", "attempt", attempt=n):
                    plan = self.physical_plan(pcfg)
                    guard(plan)
                    return execute_plan(plan)

            out = self._retry_on_overflow(
                self.ctx.config.planner, None, attempt
            )
            call.span.set(retries=self.last_retry_count)
            return self._tagged(out, call.request)

    def _retry_on_overflow(self, pcfg: PlannerConfig, dcfg, attempt):
        """The re-plan loop of every tier: ``attempt(n, pcfg, dcfg,
        guard)`` plans, calls ``guard(plan)`` (`_overflow_retry_guard`)
        and executes attempt ``n``, all of it inside that tier's own span
        or scope. Planning belongs inside: scalar subqueries execute at
        plan time and their overflows must trigger the same retry. A
        capacity overflow, raised here or on a worker, widens the knobs
        it implicates (all of them on the last widening) and tries
        again, ``overflow_retries`` times; anything else passes through.
        -> the attempt's result, with ``last_retry_count`` set."""
        from datafusion_distributed_tpu.runtime.errors import (
            QueryError,
            is_capacity_overflow,
        )

        retries = self.ctx.config.overflow_retries
        last_err: Optional[Exception] = None
        for n in range(retries + 1):
            try:
                out = attempt(
                    n, pcfg, dcfg,
                    lambda plan: _overflow_retry_guard(plan, n, last_err),
                )
            except QueryError as e:
                if not is_capacity_overflow(e):
                    raise
                last_err = e
                # widen in place so every other customized field survives
                # the retry (session SET options, skew factor included)
                pcfg, dcfg = _widen_for_overflow(
                    pcfg, dcfg, e, force_all=n >= retries - 1
                )
            else:
                self.last_retry_count = n  # observability (sweeps)
                return out
        raise last_err  # type: ignore[misc]

    def _trace_call(self, name: str):
        return tracing.trace_call(
            name, self.ctx.config.distributed_options, self.request_id
        )

    def _tagged(self, out: Table, request: Optional[str]) -> Table:
        """A collect's result. Traced (``request`` is its trace's), it
        carries the request's identifier, so that the fetch of a bare
        Table joins its request: on a Table object of the result's own,
        never on ``out`` itself, which a result cache may hold too and
        hand to a later, untraced hit."""
        if request is None:
            return out
        self.request_id = request
        return tracing.tag_request(
            Table(out.names, out.columns, out.num_rows), request
        )

    def collect(self):
        """-> pyarrow Table with user-facing column names."""
        return table_to_arrow(self._strip_quals(self.collect_table()))

    def to_pandas(self):
        return self._strip_quals(self.collect_table()).to_pandas()

    @staticmethod
    def _strip_quals(t: Table) -> Table:
        names = []
        seen = set()
        for n in t.names:
            short = n.split(".")[-1] if "." in n else n
            # duplicate short names (SELECT c.x, o.x) keep their qualifier
            names.append(n if short in seen else short)
            seen.add(short)
        return tracing.tag_request(
            Table(tuple(names), t.columns, t.num_rows),
            tracing.request_of(t),
        )

    # -- distributed execution -------------------------------------------------
    def distributed_plan(self, num_tasks: int = 8, config=None,
                         planner_config: Optional[PlannerConfig] = None,
                         mesh=None, eager_subqueries: bool = False,
                         coordinator=None):
        from datafusion_distributed_tpu.planner.distributed import (
            DistributedConfig,
            distribute_plan,
            merge_direct_groupings,
        )

        if config is None:
            opts = {
                k: v
                for k, v in self.ctx.config.distributed_snapshot().items()
                if k in DistributedConfig.__dataclass_fields__
            }
            opts.setdefault("num_tasks", num_tasks)
            config = DistributedConfig(**opts)
        cfg = config
        pcfg = planner_config or self.ctx.config.planner
        # EVERY plan-shaping config field keys the cache (a hand-picked
        # subset silently served stale plans when e.g. max_tasks_per_stage
        # changed via SET); the unhashable estimator keys by identity
        cfg_key = tuple(
            id(v) if k == "task_estimator" else v
            for k, v in (
                (k, getattr(cfg, k))
                for k in type(cfg).__dataclass_fields__
            )
        )
        from datafusion_distributed_tpu.plan.verify import (
            enforce_verification,
        )

        verify_kw = dict(
            options=self.ctx.config.distributed_options,
            mesh_axis_size=(mesh.shape["tasks"] if mesh is not None
                            else None),
            context="distributed plan",
        )
        key = ("dist", cfg_key, self._pcfg_key(pcfg), mesh is not None,
               eager_subqueries, coordinator is not None)
        plan = self._plan_cache_get(key)
        if plan is not None:
            enforce_verification(plan, **verify_kw)
            return plan
        subquery_executor = None
        if mesh is not None:
            from datafusion_distributed_tpu.runtime.mesh_executor import (
                execute_on_mesh,
            )

            def subquery_executor(p):
                return execute_on_mesh(distribute_plan(p, cfg), mesh)
        elif coordinator is not None:
            # Plans shipped to workers must be self-contained, AND the
            # subquery must run through the SAME distributed path as the
            # outer query: f32 sums are only bitwise-reproducible under an
            # identical task split (TPC-H q15 compares them for equality).
            def subquery_executor(p):
                return coordinator.execute(distribute_plan(p, cfg))
        elif eager_subqueries:
            # Plans shipped to workers must be self-contained: lazy
            # ScalarSubqueryExpr nodes cannot cross the wire codec, so
            # uncorrelated scalar subqueries resolve to constants at plan
            # time (single-node — their results are scalars).
            def subquery_executor(p):
                return execute_plan(p)

        planner = PhysicalPlanner(self.ctx.catalog, pcfg, subquery_executor)
        plan = distribute_plan(planner.plan(self.logical), cfg)
        if mesh is not None:
            plan = merge_direct_groupings(plan, mesh.shape["tasks"])
        self._plan_cache_put(key, plan)
        enforce_verification(plan, **verify_kw)
        return plan

    def collect_distributed_table(self, num_tasks: Optional[int] = None,
                                  mesh=None) -> Table:
        """Execute over a jax Mesh: the whole staged plan compiles into one
        SPMD program (see runtime/mesh_executor.py). Overflow -> re-plan with
        widened capacities, like collect_table."""
        import jax as _jax

        from datafusion_distributed_tpu.planner.distributed import DistributedConfig
        from datafusion_distributed_tpu.runtime.mesh_executor import (
            execute_on_mesh,
            make_mesh,
        )

        if mesh is None:
            mesh = make_mesh(num_tasks or len(_jax.devices()))
        t = mesh.shape["tasks"]
        pcfg = self.ctx.config.planner
        # uniform_stage_tasks: one SPMD program's exchanges are axis-wide
        # collectives, so every stage runs at the physical mesh width —
        # per-stage lattice knobs apply to the host/coordinator tier
        dcfg = replace(
            self._seeded_distributed_config(t), uniform_stage_tasks=True
        )
        with self._trace_call("query") as call:
            def attempt(n, pcfg, dcfg, guard):
                with call.tracer.span("attempt", "attempt", attempt=n):
                    plan = self.distributed_plan(t, dcfg, pcfg, mesh=mesh)
                    guard(plan)
                    return execute_on_mesh(plan, mesh)

            out = self._retry_on_overflow(pcfg, dcfg, attempt)
            call.span.set(retries=self.last_retry_count)
            return self._tagged(out, call.request)

    def _seeded_distributed_config(self, num_tasks: int):
        """DistributedConfig honoring the session's `SET distributed.*`
        options (the reference's ConfigExtension flow; previously
        collect_distributed_table silently bypassed them)."""
        from datafusion_distributed_tpu.planner.distributed import (
            DistributedConfig,
        )

        opts = {
            k: v for k, v in self.ctx.config.distributed_snapshot().items()
            if k in DistributedConfig.__dataclass_fields__
        }
        opts["num_tasks"] = num_tasks
        return DistributedConfig(**opts)

    def _seeded_host_config(self, num_tasks: int):
        """Like _seeded_distributed_config, but for the host/coordinator
        tier where task counts are real scheduling units: bytes-based
        sizing is on by default (SET distributed.size_tasks_to_data=false
        opts out)."""
        cfg = self._seeded_distributed_config(num_tasks)
        if "size_tasks_to_data" not in self.ctx.config.distributed_options:
            cfg = replace(cfg, size_tasks_to_data=True)
        return cfg

    def _result_cache_key(self, num_tasks: int):
        """Whole-result cache key for this query at the session's live
        configuration (plan/fingerprint.py result_cache_key): the
        post-hoist staged-plan fingerprint + literal parameter vectors,
        extended with the full PlannerConfig snapshot, the catalog
        generation, and the task profile (f32 sums are only bitwise-
        reproducible under an identical task split, so a profile change
        must miss). None when caching cannot apply (unfingerprintable
        plan — e.g. unresolved scalar subqueries)."""
        from datafusion_distributed_tpu.plan.fingerprint import (
            result_cache_key,
        )

        try:
            plan = self.distributed_plan(
                num_tasks, self._seeded_host_config(num_tasks),
                self.ctx.config.planner,
            )
            return result_cache_key(plan, extra=(
                self._pcfg_key(self.ctx.config.planner),
                self.ctx.catalog.generation,
                int(num_tasks),
            ))
        except Exception:
            return None

    def collect_coordinated_table(
        self,
        coordinator=None,
        num_workers: int = 2,
        num_tasks: int = 4,
        adaptive: bool = False,
    ) -> Table:
        """Execute through the host Coordinator/Worker runtime (the cross-
        host DCN tier) instead of a single SPMD mesh program. With no
        ``coordinator`` an in-memory cluster of ``num_workers`` is spun up —
        the reference's InMemoryChannelResolver rung its whole TPC suite
        runs on (`tpch_correctness_test.rs:23-80`). ``adaptive=True`` uses
        the AdaptiveCoordinator (the reference's dynamic task count).

        With `SET distributed.result_cache` on, the whole-result cache
        is consulted FIRST (runtime/result_cache.py): a hit returns the
        staged result by reference — no cluster, no coordinator, no
        execution, zero new XLA traces. Concurrent submissions of one
        key single-flight: one executes, the rest block for its fill."""
        rc = self.ctx.result_cache()
        key = self._result_cache_key(num_tasks) if rc is not None else None
        if key is None:
            return self._tagged(*self._collect_coordinated_uncached(
                coordinator, num_workers, num_tasks, adaptive
            ))
        state, cached = rc.begin(key)
        if state == "hit":
            return cached
        try:
            out, request = self._collect_coordinated_uncached(
                coordinator, num_workers, num_tasks, adaptive
            )
        except BaseException:
            rc.fail(key)
            raise
        rc.fill(key, out)
        return self._tagged(out, request)

    def _collect_coordinated_uncached(
        self,
        coordinator=None,
        num_workers: int = 2,
        num_tasks: int = 4,
        adaptive: bool = False,
    ) -> tuple:
        """-> (the result, the request of its trace or None where it was
        not traced)."""
        from datafusion_distributed_tpu.runtime.coordinator import (
            AdaptiveCoordinator,
            Coordinator,
            InMemoryCluster,
        )

        if coordinator is None:
            cluster = InMemoryCluster(num_workers)
            cls = AdaptiveCoordinator if adaptive else Coordinator
            coordinator = cls(
                resolver=cluster, channels=cluster,
                config_options=self.ctx.config.distributed_snapshot(),
                passthrough_headers=dict(self.ctx.config.passthrough_headers),
            )
        if getattr(coordinator, "result_cache", None) is None:
            # cross-query sub-plan frontier sharing rides the same
            # coordinator hook as checkpoint restore (None when the
            # result_cache knob is off)
            coordinator.result_cache = self.ctx.result_cache()
        adaptive_coord = hasattr(coordinator, "pin_overflow_headroom")

        def attempt(n, pcfg, dcfg, guard):
            if adaptive_coord and n:
                # widen-and-pin for the retry (see
                # AdaptiveCoordinator.pin_overflow_headroom: subquery
                # successes through the same coordinator must not reset
                # the widened headroom mid-attempt)
                coordinator.pin_overflow_headroom(n)
            # an attempt here is one `Coordinator.execute`, a trace of
            # its own: its root carries the request and the attempt, and
            # the one that succeeds the retries
            scope = tracing.request_scope(self.request_id, attempt=n)
            try:
                with scope:
                    plan = self.distributed_plan(
                        num_tasks, dcfg, pcfg, coordinator=coordinator
                    )
                    refuse_partitioned_scans(plan, "the coordinator tier")
                    guard(plan)
                    out = coordinator.execute(plan)
            finally:
                # a retry's trace joins the request of this one
                self.request_id = scope.request
            traced = (
                getattr(coordinator, "trace_store", None)
                or tracing.DEFAULT_TRACE_STORE
            ).annotate(getattr(coordinator, "last_query_id", None),
                       retries=n)
            return out, scope.request if traced else None

        try:
            return self._retry_on_overflow(
                self.ctx.config.planner, self._seeded_host_config(num_tasks),
                attempt,
            )
        finally:
            if adaptive_coord:
                coordinator.release_overflow_headroom()

    def collect_coordinated(self, **kw):
        return table_to_arrow(
            self._strip_quals(self.collect_coordinated_table(**kw))
        )

    def collect_distributed(self, num_tasks: Optional[int] = None, mesh=None):
        return table_to_arrow(
            self._strip_quals(self.collect_distributed_table(num_tasks, mesh))
        )

    def explain(self) -> str:
        return self.physical_plan().display_tree()

    def explain_verify(self, num_tasks: Optional[int] = None,
                       mesh_axis_size: Optional[int] = None
                       ) -> "VerifyReport":
        """The `EXPLAIN VERIFY` surface: the STAGED plan annotated with
        every verifier diagnostic per node (plan/verify.py), plus the
        single-node plan's diagnostics when they differ. Never raises on a
        malformed plan — the whole point is to show what strict mode would
        reject."""
        from datafusion_distributed_tpu.plan.verify import (
            render_verified_tree,
            verify_physical_plan,
        )

        t = num_tasks or int(
            self.ctx.config.distributed_options.get("num_tasks", 8)
        )
        plan = self._plan_without_enforce(t)
        result = verify_physical_plan(plan, mesh_axis_size=mesh_axis_size)
        return VerifyReport(render_verified_tree(plan, result), result)

    def _plan_without_enforce(self, num_tasks: int):
        """Build the staged plan with enforcement suppressed: EXPLAIN
        VERIFY must render a strict-mode-rejected plan, not die on it."""
        opts = self.ctx.config.distributed_options
        saved = opts.get("verify_plans")
        opts["verify_plans"] = "off"
        try:
            return self.distributed_plan(
                num_tasks, self._seeded_distributed_config(num_tasks),
                self.ctx.config.planner,
            )
        finally:
            if saved is None:
                opts.pop("verify_plans", None)
            else:
                opts["verify_plans"] = saved

    def explain_distributed(self, num_tasks: int = 8) -> str:
        from datafusion_distributed_tpu.planner.distributed import (
            display_staged_plan,
        )

        return display_staged_plan(self.distributed_plan(num_tasks))

    def logical_display(self) -> str:
        return self.logical.display_tree()


class VerifyReport(str):
    """The result of `EXPLAIN VERIFY` / `DataFrame.explain_verify`: renders
    as the annotated plan tree; `.result` carries the structured
    VerifyResult and `.diagnostics` the raw Diagnostic list."""

    def __new__(cls, text: str, result):
        obj = super().__new__(cls, text)
        obj.result = result
        obj.diagnostics = result.diagnostics
        return obj


class SessionContext:
    def __init__(self, config: Optional[SessionConfig] = None):
        import threading

        self.catalog = Catalog()
        self.config = config or SessionConfig()
        # session-level physical-plan cache, keyed by (logical-plan
        # fingerprint, catalog generation, planner knobs): distinct
        # ctx.sql(text) submissions of the same query reuse the planned
        # tree (and therefore every downstream compiled-program cache
        # entry) instead of re-planning. Bounded LRU: entries pin scan
        # Tables that may since have been de-registered. Locked: the
        # serving tier plans concurrent submissions from N client/driver
        # threads against this one cache.
        self._plans: dict = {}
        self._plans_lock = threading.Lock()
        # fingerprint-keyed whole-result + sub-plan cache (runtime/
        # result_cache.py), created lazily on the first consult with
        # `SET distributed.result_cache` on; _plans_lock guards creation
        self._result_cache = None  # guarded-by: _plans_lock

    _PLAN_CACHE_ENTRIES = 128

    def result_cache(self):
        """The session's ResultCache when `SET distributed.result_cache`
        is on, else None. Every consult reconciles the cache with the
        live catalog generation (lazy invalidation — covers table
        registrations that bypassed SessionContext.register_table) and
        the `result_cache_budget_bytes` knob."""
        from datafusion_distributed_tpu.ops.table import parse_bool_knob

        opts = self.config.distributed_options
        try:
            if not parse_bool_knob(opts.get("result_cache", False)):
                return None
        except ValueError:
            return None
        rc = self._result_cache
        if rc is None:
            from datafusion_distributed_tpu.runtime.result_cache import (
                ResultCache,
            )

            with self._plans_lock:
                rc = self._result_cache
                if rc is None:
                    rc = self._result_cache = ResultCache()
        rc.sync(
            generation=self.catalog.generation,
            budget_bytes=opts.get("result_cache_budget_bytes", 0),
        )
        return rc

    def _plan_cache_get(self, key):
        with self._plans_lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.pop(key)
                self._plans[key] = plan  # move-to-end: LRU
            return plan

    def _plan_cache_put(self, key, plan) -> None:
        with self._plans_lock:
            # ``key[1]`` is the catalog generation (`DataFrame.
            # _plan_cache_put`), which only grows: a plan of an older one
            # is never asked for again, and goes with what its leaves pin
            # on the devices (task slices, a mesh placement)
            for stale in [k for k in self._plans if k[1] < key[1]]:
                del self._plans[stale]
            while len(self._plans) >= self._PLAN_CACHE_ENTRIES:
                self._plans.pop(next(iter(self._plans)))
            self._plans[key] = plan

    # -- registration ---------------------------------------------------------
    def register_parquet(self, name: str, paths, capacity: Optional[int] = None):
        import pyarrow as pa
        import pyarrow.parquet as pq

        if isinstance(paths, (str,)):
            paths = [paths]
        tables = [pq.read_table(p) for p in paths]
        arrow = pa.concat_tables(tables) if len(tables) > 1 else tables[0]
        self.register_table(name, arrow_to_table(arrow, capacity=capacity))

    def register_arrow(self, name: str, arrow_table, capacity=None):
        """Register a pyarrow Table on the default device, or a table given
        as `Partitions` with partition i resident on ``jax.devices()[i]``
        (`io/parquet.py arrow_to_partitions`), which only the mesh tier
        reads: `DataFrame.collect_distributed` over a mesh of the devices
        that hold it."""
        # where a table's registration goes, for whoever waits for it: a
        # `register` span, under it an `encode` a string column (host
        # dictionary encoding) and the `h2d` of the padded columns, one a
        # partition
        with tracing.trace_call("register", self.config.distributed_options,
                                table=name,
                                rows=arrow_table.num_rows) as call:
            if isinstance(arrow_table, Partitions):
                import jax

                table = arrow_to_partitions(arrow_table, jax.devices(),
                                            capacity, tracer=call.tracer)
                call.span.set(partitions=len(table.parts))
            else:
                table = arrow_to_table(arrow_table, capacity,
                                       tracer=call.tracer)
            call.span.set(capacity=table.capacity)
            self.register_table(name, table)

    def table_masks(self, name: str) -> int:
        """The validity arrays the registered table holds on the device:
        one a column that holds a NULL, none for a column without (what a
        traced registration's ``h2d`` span reads as ``masks``)."""
        return self.catalog.tables[name.lower()].validity_masks

    def register_table(self, name: str, table: Table | PartitionedTable):
        self.catalog.register_table(name, table)
        rc = self._result_cache
        if rc is not None:
            # eager half of result-cache invalidation: the generation
            # bump above makes every cached entry (whole-result AND
            # sub-plan frontier) stale — drop them NOW so a post-update
            # query can never be served pre-update rows
            rc.invalidate_generation(self.catalog.generation)

    # -- SQL ------------------------------------------------------------------
    def sql(self, query: str) -> DataFrame:
        with tracing.trace_call("sql",
                                self.config.distributed_options) as call:
            with call.tracer.span("parse", "parse"):
                stmts = parse_statements(query)
            with call.tracer.span("plan", "plan"):
                return self._bind_statements(stmts, call.request)

    def _bind_statements(self, stmts, request_id: Optional[str]):
        result: Optional[DataFrame] = None
        views: dict[str, LogicalPlan] = dict(self.catalog.views)
        for stmt in stmts:
            if isinstance(stmt, CreateView):
                binder = Binder(_ViewCatalog(self.catalog, views), views)
                plan = binder.bind(stmt.query)
                if stmt.column_aliases:
                    from datafusion_distributed_tpu.plan import expressions as pe
                    from datafusion_distributed_tpu.sql.logical import LProject

                    fields = plan.schema().fields
                    if len(stmt.column_aliases) != len(fields):
                        raise ValueError("view column alias arity mismatch")
                    plan = LProject(
                        [(pe.Col(f.name), n)
                         for f, n in zip(fields, stmt.column_aliases)],
                        plan,
                    )
                views[stmt.name.lower()] = plan
                self.catalog.views[stmt.name.lower()] = plan
            elif isinstance(stmt, DropView):
                views.pop(stmt.name.lower(), None)
                self.catalog.views.pop(stmt.name.lower(), None)
            elif isinstance(stmt, SetOption):
                self.config.set_option(stmt.name, stmt.value)
            elif isinstance(stmt, ExplainVerify):
                binder = Binder(_ViewCatalog(self.catalog, views), views)
                # keep looping: statements after EXPLAIN VERIFY in a
                # multi-statement script still execute; the report is the
                # script's result only when it is the last statement
                result = DataFrame(self, binder.bind(stmt.query)).explain_verify()
            else:
                binder = Binder(_ViewCatalog(self.catalog, views), views)
                result = DataFrame(self, binder.bind(stmt), request_id)
        if result is None:
            if stmts:
                return None  # DDL/SET-only script
            raise ValueError("no SQL statements in input")
        return result

    def last_trace(self):
        """Chrome trace-event JSON dict of the most recently completed
        traced query (load in Perfetto / chrome://tracing), or None when
        nothing ran with `SET distributed.tracing` on. Coordinated
        executions record into the process-wide trace store regardless of
        which coordinator object ran them (runtime/tracing.py)."""
        from datafusion_distributed_tpu.runtime.tracing import (
            DEFAULT_TRACE_STORE,
            to_chrome_trace,
        )

        trace = DEFAULT_TRACE_STORE.last()
        return to_chrome_trace(trace) if trace is not None else None

    def last_trace_profile(self) -> str:
        """Text profile report of the most recent traced query ('' when
        none) — the explain_analyze trace fold, standalone."""
        from datafusion_distributed_tpu.runtime.tracing import (
            DEFAULT_TRACE_STORE,
            render_profile,
        )

        trace = DEFAULT_TRACE_STORE.last()
        return render_profile(trace) if trace is not None else ""

    def prepare(self, template: str) -> PreparedStatement:
        """Prepared-statement API: ``ctx.prepare("... where x < $1")``
        -> a PreparedStatement whose ``execute(params)`` /
        ``submit(serving_session, params)`` bindings share one compiled
        program per stage via the literal-hoisting + fingerprint
        machinery (plan/fingerprint.py) — zero compiles at serving time
        after the first execution."""
        return PreparedStatement(self, template)


def _parse_placeholders(template: str) -> list:
    """-> [(literal_text | None, param_name | None)] segments of a
    prepared-statement template. Placeholders are ``$name`` or ``$1``-style
    (1-based positional); ``$`` inside single-quoted SQL string literals,
    double-quoted identifiers, and ``--`` / ``/* */`` comments is text,
    not a placeholder (standard '' / "" escaping respected)."""
    import re as _re

    out: list = []
    buf: list = []
    i, n = 0, len(template)
    ph = _re.compile(r"\$([A-Za-z_][A-Za-z0-9_]*|[0-9]+)")
    while i < n:
        c = template[i]
        if c in ("'", '"'):
            q = c
            j = i + 1
            while j < n:
                if template[j] == q:
                    if j + 1 < n and template[j + 1] == q:
                        j += 2
                        continue
                    break
                j += 1
            buf.append(template[i:j + 1])
            i = j + 1
        elif c == "-" and template[i:i + 2] == "--":
            j = template.find("\n", i)
            j = n if j < 0 else j
            buf.append(template[i:j])
            i = j
        elif c == "/" and template[i:i + 2] == "/*":
            j = template.find("*/", i + 2)
            j = n if j < 0 else j + 2
            buf.append(template[i:j])
            i = j
        elif c == "$":
            m = ph.match(template, i)
            if m:
                if buf:
                    out.append(("".join(buf), None))
                    buf = []
                out.append((None, m.group(1)))
                i = m.end()
            else:
                buf.append(c)
                i += 1
        else:
            buf.append(c)
            i += 1
    if buf:
        out.append(("".join(buf), None))
    return out


def _format_param(value) -> str:
    """SQL literal text for a bound parameter value. Numeric and date
    parameters become exactly the literals the template author would have
    written — so the PR 2 literal hoist lifts them into the runtime
    parameter vectors and every binding shares one compiled program."""
    import datetime as _dt

    if value is None:
        return "NULL"
    if isinstance(value, bool):
        return "true" if value else "false"
    if isinstance(value, int):
        return str(value)
    if isinstance(value, float):
        return repr(value)
    if isinstance(value, _dt.datetime):
        # DATE32 is the engine's only temporal type: a datetime binds as
        # its date ONLY when that loses nothing — a nonzero time-of-day
        # silently admitting/excluding a day's rows must be an error
        if (value.hour or value.minute or value.second
                or value.microsecond or value.tzinfo is not None):
            raise TypeError(
                "datetime parameters with a time-of-day (or tzinfo) are "
                "not supported — the engine's temporal type is DATE32; "
                "pass a datetime.date"
            )
        return f"date '{value.date().isoformat()}'"
    if isinstance(value, _dt.date):
        return f"date '{value.isoformat()}'"
    if isinstance(value, str):
        return "'" + value.replace("'", "''") + "'"
    raise TypeError(
        f"unsupported prepared-statement parameter type "
        f"{type(value).__name__}"
    )


class PreparedStatement:
    """A parameterized query template (``ctx.prepare(sql)``) riding the
    cross-query compile-reuse machinery: every ``execute(params)`` binds
    the parameter values as literals, and because the PR 2 literal hoist
    lifts numeric/date comparison literals into runtime parameter vectors
    keyed out of the plan fingerprint, all bindings of one template share
    ONE compiled program per stage — zero new XLA compiles at serving
    time after the first (warming) execution. String parameters bind too,
    but distinct string values fingerprint distinctly (their evaluation
    is trace-time dictionary work) and compile per distinct value.

    Placeholders: ``$name`` (bind with a dict / kwargs) or ``$1..$n``
    (bind with a sequence). `warm()` runs the first (compiling) execution
    eagerly so serving-path submissions are execute-bound from the start.
    """

    def __init__(self, ctx: "SessionContext", template: str):
        self.ctx = ctx
        self.template = template
        self._segments = _parse_placeholders(template)
        names: list[str] = []
        for _text, name in self._segments:
            if name is not None and name not in names:
                names.append(name)
        if not names:
            raise ValueError(
                "prepared statement has no $placeholders — use ctx.sql()"
                " for parameter-free queries"
            )
        self.param_names = names
        self.positional = all(n.isdigit() for n in names)

    def _mapping(self, params, kw) -> dict:
        if params is None:
            mapping = dict(kw)
        elif isinstance(params, dict):
            mapping = {**params, **kw}
        elif isinstance(params, (list, tuple)):
            if not self.positional:
                raise ValueError(
                    "sequence parameters require $1..$n placeholders; "
                    f"this template names {self.param_names}"
                )
            mapping = {str(i + 1): v for i, v in enumerate(params)}
            mapping.update(kw)
        else:
            raise TypeError(
                "params must be a dict, a sequence, or keyword arguments"
            )
        missing = [n for n in self.param_names if n not in mapping]
        if missing:
            raise ValueError(f"missing parameters: {missing}")
        return mapping

    def bind_sql(self, params=None, **kw) -> str:
        """The template with every placeholder bound as a SQL literal."""
        mapping = self._mapping(params, kw)
        return "".join(
            text if name is None else _format_param(mapping[name])
            for text, name in self._segments
        )

    def to_df(self, params=None, **kw) -> "DataFrame":
        """Plan the bound statement (session plan cache applies)."""
        return self.ctx.sql(self.bind_sql(params, **kw))

    def execute(self, params=None, **kw):
        """Single-process execution -> pyarrow Table."""
        return self.to_df(params, **kw).collect()

    def execute_coordinated(self, params=None, coordinator=None,
                            num_workers: int = 2, num_tasks: int = 4,
                            **kw):
        """Distributed (host-runtime tier) execution -> pyarrow Table."""
        return self.to_df(params, **kw).collect_coordinated(
            coordinator=coordinator, num_workers=num_workers,
            num_tasks=num_tasks,
        )

    def submit(self, session, params=None, priority: int = 0, **kw):
        """Submit a binding to a ServingSession -> QueryHandle (the
        serving hot path: parse + bind + plan-cache hit + fingerprint-
        keyed program reuse, no compiles after warm())."""
        return session.submit(self.bind_sql(params, **kw),
                              priority=priority)

    def warm(self, params=None, **kw) -> "PreparedStatement":
        """Run the first (compiling) execution now; subsequent bindings
        are execute-bound. -> self, for chaining."""
        self.execute(params, **kw)
        return self


class _ViewCatalog:
    """Catalog facade that also resolves registered views (as CTEs)."""

    def __init__(self, catalog: Catalog, views: dict):
        self.catalog = catalog
        self.views = views

    def has_table(self, name: str) -> bool:
        return self.catalog.has_table(name) or name.lower() in self.views

    def table_schema(self, name: str) -> Schema:
        if name.lower() in self.views:
            s = self.views[name.lower()].schema()
            from datafusion_distributed_tpu.schema import Field

            return Schema(
                [Field(f.name.split(".")[-1], f.dtype, f.nullable)
                 for f in s.fields]
            )
        return self.catalog.table_schema(name)

    def table_rows(self, name: str) -> int:
        if name.lower() in self.views:
            return 1000
        return self.catalog.table_rows(name)

    def column_ndv(self, table: str, column: str):
        if table.lower() in self.views:
            return None
        return self.catalog.column_ndv(table, column)

    def column_domain(self, table: str, column: str):
        if table.lower() in self.views:
            return None
        return self.catalog.column_domain(table, column)

    def scan_exec(self, name: str, columns):
        return self.catalog.scan_exec(name, columns)
