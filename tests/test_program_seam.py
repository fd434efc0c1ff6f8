"""The seam between a plan and its compiled program, on all three
executors (`execute_plan`, `execute_on_mesh`, `execute_stage_span_on_mesh`):
one tracer (`plan/physical.py trace_plan`, `ProgramTrace`), one typed
overflow (`runtime/errors.py`), one re-plan loop
(`DataFrame._retry_on_overflow`), counters declared once
(`spans.PROGRAM_COUNTERS`)."""

import jax
import numpy as np
import pyarrow as pa
import pytest
from jax.sharding import Mesh

from datafusion_distributed_tpu import precision, spans
from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.plan import physical as phys
from datafusion_distributed_tpu.plan.physical import (
    HashAggregateExec,
    MemoryScanExec,
    execute_plan,
)
from datafusion_distributed_tpu.runtime import mesh_worker, tracing
from datafusion_distributed_tpu.runtime.errors import (
    CapacityOverflowError,
    PrecisionRangeError,
    WorkerError,
    is_capacity_overflow,
)
from datafusion_distributed_tpu.runtime.mesh_executor import (
    execute_on_mesh,
    make_mesh,
)
from datafusion_distributed_tpu.sql import context as sql_context
from datafusion_distributed_tpu.sql.context import (
    OverflowRetryAbandoned,
    SessionContext,
)

TASKS = 2


def _agg_plan(num_slots, keys=64, rows=256):
    """GROUP BY an integer key (the claim loop) over one slice a task."""
    rng = np.random.default_rng(11)
    slices = [
        arrow_to_table(pa.table({
            "k": rng.permutation(rows) % keys,
            "v": rng.normal(size=rows),
        }))
        for _ in range(TASKS)
    ]
    scan = MemoryScanExec(slices, slices[0].schema())
    return HashAggregateExec(
        "single", ["k"], [AggSpec("sum", "v", "sv")], scan, num_slots
    )


def _span_mesh():
    return Mesh(np.asarray(jax.devices()[:TASKS]), (mesh_worker.AXIS,))


_EXECUTORS = {
    "direct": (
        lambda plan: execute_plan(plan, use_cache=False),
        "hash table overflow in plan (nodes: ['HashAggregate']); "
        "re-plan with more slots",
    ),
    "mesh": (
        lambda plan: execute_on_mesh(plan, make_mesh(TASKS)),
        "exchange/hash capacity overflow on mesh (nodes: "
        "['HashAggregate']); re-plan with larger capacities",
    ),
    "span": (
        lambda plan: mesh_worker.execute_stage_span_on_mesh(
            plan, _span_mesh(), TASKS, TASKS
        ),
        "hash table overflow in span program (nodes: ['HashAggregate']); "
        "re-plan with more slots",
    ),
}


@pytest.mark.parametrize("executor", sorted(_EXECUTORS))
def test_capacity_overflow_is_typed_by_executor(executor):
    """64 groups into 8 slots: every executor raises the one typed error,
    with the overflowing program's nodes and the text it always had."""
    run, text = _EXECUTORS[executor]
    run(_agg_plan(num_slots=256))  # wide enough: no error
    with pytest.raises(CapacityOverflowError) as e:
        run(_agg_plan(num_slots=8))
    assert e.value.nodes == ["HashAggregate"]
    assert str(e.value) == text
    assert is_capacity_overflow(e.value)


def _int_sum_ctx():
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({
        "k": np.zeros(8, dtype=np.int32),
        "v": np.full(8, 2**29, dtype=np.int32),
    }))
    return ctx


_COLLECTS = {
    "direct": lambda df: df.collect_table(),
    "mesh": lambda df: df.collect_distributed_table(mesh=make_mesh(TASKS)),
}


@pytest.mark.skipif(precision.MODE != "tpu", reason="tpu mode only")
@pytest.mark.parametrize("tier", sorted(_COLLECTS))
def test_precision_range_is_typed_and_never_retried(tier, monkeypatch):
    attempts = []
    guard = sql_context._overflow_retry_guard
    monkeypatch.setattr(
        sql_context, "_overflow_retry_guard",
        lambda plan, n, err: (attempts.append(n), guard(plan, n, err)),
    )
    df = _int_sum_ctx().sql("select k, sum(v) as sv from t group by k")
    with pytest.raises(PrecisionRangeError, match="DFTPU_PRECISION=x64") as e:
        _COLLECTS[tier](df)
    assert attempts == [0]
    assert not is_capacity_overflow(e.value)


_OVERFLOW_TEXT = _EXECUTORS["direct"][1]


@pytest.mark.parametrize("flavour", ["local", "wire"])
def test_is_capacity_overflow_reads_the_type_not_the_text(flavour):
    if flavour == "local":
        err = CapacityOverflowError(_OVERFLOW_TEXT, ["HashAggregate"])
        # a text that says "overflow" is not one
        assert not is_capacity_overflow(RuntimeError(_OVERFLOW_TEXT))
        assert not is_capacity_overflow(
            WorkerError(_OVERFLOW_TEXT, original_type="RuntimeError")
        )
    else:
        from datafusion_distributed_tpu.runtime.errors import (
            wrap_worker_exception,
        )

        wrapped = wrap_worker_exception(
            CapacityOverflowError(_OVERFLOW_TEXT, ["HashAggregate"]),
            "mem://w0", None,
        )
        err = WorkerError.from_dict(wrapped.to_dict())
        assert type(err) is WorkerError
        assert _OVERFLOW_TEXT in str(err)
    assert is_capacity_overflow(err)


class _StubCoordinator:
    """What `_collect_coordinated_uncached` asks of a coordinator."""

    result_cache = None
    last_query_id = None

    def __init__(self, execute):
        self.execute = execute


def _always(err, calls):
    def execute(*_a, **_k):
        calls.append(1)
        raise err
    return execute


def _collect(tier, df, execute, monkeypatch):
    if tier == "direct":
        monkeypatch.setattr(sql_context, "execute_plan", execute)
        return df.collect_table()
    if tier == "mesh":
        from datafusion_distributed_tpu.runtime import mesh_executor

        monkeypatch.setattr(mesh_executor, "execute_on_mesh", execute)
        return df.collect_distributed_table(mesh=make_mesh(TASKS))
    return df.collect_coordinated_table(
        coordinator=_StubCoordinator(execute), num_tasks=TASKS
    )


@pytest.mark.parametrize("tier", ["direct", "mesh", "coordinated"])
def test_retry_policy_is_one_loop_for_every_tier(tier, monkeypatch):
    """`overflow_retries + 1` attempts, everything widened on the last
    widenings, the overflow re-raised when they run out; a worker's
    overflow counts as one; `OverflowRetryAbandoned` passes through."""
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({"k": np.arange(64) % 4,
                                      "v": np.arange(64.0)}))
    retries = ctx.config.overflow_retries
    forced = []
    widen = sql_context._widen_for_overflow
    monkeypatch.setattr(
        sql_context, "_widen_for_overflow",
        lambda p, d, e, force_all=False: (
            forced.append(force_all), widen(p, d, e, force_all))[1],
    )
    err = (
        CapacityOverflowError(_OVERFLOW_TEXT, ["HashAggregate"])
        if tier != "coordinated"
        else WorkerError(_OVERFLOW_TEXT,
                         original_type="CapacityOverflowError")
    )
    calls = []
    df = ctx.sql("select k, sum(v) as sv from t group by k")
    with pytest.raises(type(err)) as e:
        _collect(tier, df, _always(err, calls), monkeypatch)
    assert e.value is err
    assert len(calls) == retries + 1
    assert forced == [n >= retries - 1 for n in range(retries + 1)]

    calls.clear()
    abandoned = OverflowRetryAbandoned("overflow-retry abandoned: overflow")
    with pytest.raises(OverflowRetryAbandoned):
        _collect(tier, df, _always(abandoned, calls), monkeypatch)
    assert len(calls) == 1


@pytest.mark.parametrize("tier,span", [("direct", "execute"),
                                       ("mesh", "mesh.execute")])
def test_a_counter_is_one_name(tier, span, monkeypatch):
    """A name added to `spans.PROGRAM_COUNTERS` is on the executor's span
    and, as a zero, in every row of `layer_report()`, with no other
    edit."""
    monkeypatch.setattr(
        spans, "PROGRAM_COUNTERS", spans.PROGRAM_COUNTERS + ("probe_only",)
    )
    ctx = SessionContext()
    # a plan no other test runs: a program-cache hit would hand back the
    # counters of a trace that predates the name
    ctx.register_arrow("t", pa.table({"k": np.arange(96) % 3,
                                      "v": np.arange(96.0),
                                      "probe_only": np.arange(96)}))
    ctx.sql("set distributed.tracing = 'on'")
    store = tracing.DEFAULT_TRACE_STORE
    before = {t.query_id for t in store.finished_traces()}
    _COLLECTS[tier](ctx.sql(
        "select k, sum(v) as sv, max(probe_only) as p from t group by k"
    ))
    mine = [t for t in store.finished_traces() if t.query_id not in before]
    found = [s for t in mine for s in t.span_list() if s.name == span]
    assert found
    for s in found:
        assert s.attrs["probe_only"] == 0
        assert set(spans.PROGRAM_COUNTERS) <= set(s.attrs)
    rows = tracing.layer_report()
    assert rows
    for row in rows:
        assert row["counters"]["probe_only"] == 0
        assert "masked_filters" in row["counters"]


def test_span_programs_carry_operator_scopes(monkeypatch):
    """The span executor traces through `trace_plan` like the other two,
    so its program's ops sit under `<NodeClass>.<pre-order position>`."""
    lowered = []
    jit = jax.jit

    def recording_jit(fun, *a, **k):
        jitted = jit(fun, *a, **k)

        def call(*args):
            lowered.append(jitted.lower(*args).as_text(debug_info=True))
            return jitted(*args)

        return call

    monkeypatch.setattr(jax, "jit", recording_jit)
    before = phys.trace_count()
    mesh_worker.execute_stage_span_on_mesh(
        _agg_plan(num_slots=256), _span_mesh(), TASKS, TASKS
    )
    monkeypatch.undo()
    text = [t for t in lowered if "HashAggregateExec" in t]
    assert text, "no span program was lowered"
    assert "HashAggregateExec.0/" in text[0]
    assert phys.trace_count() > before
