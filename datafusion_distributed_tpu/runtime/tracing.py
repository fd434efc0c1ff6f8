"""End-to-end distributed query tracing (host-side spans + events).

The reference's ObservabilityService answers *what is running where*
(Ping / GetTaskProgress / GetClusterWorkers); nothing in either engine
answered *where a query's wall time went* across
coordinator -> dispatch -> worker -> exchange. This module is that layer:
hierarchical spans ``query -> stage -> task -> attempt`` with typed child
spans for the hot phases (compile/verify, codec encode, dispatch RPC,
worker execute, exchange transfer, TableStore staging) and structured
trace *events* for every fault-path transition the engine already has
(retry, reroute, quarantine, heal, cancel, membership epoch change).

The recording half (`Span`, `Tracer`, `TraceStore`, `trace_call`, ...)
lives in the leaf module `datafusion_distributed_tpu/spans.py`, which the
layers below the runtime import; this module re-exports it and holds the
worker's side of the wire and everything that reads a trace.

Design constraints (mirrors the MetricsStore contracts):

- ALWAYS CHEAP WHEN OFF: call sites hold a `NULL_TRACER` whose methods
  are no-ops; no span objects, no clock reads, no per-task dict copies.
  `SET distributed.tracing = off|on|sampled` selects the mode per query,
  and a recording `jax.profiler` session turns every query on while it
  lasts (`resolve_tracing_mode`): capture a profile of a live server and
  the program's spans are in it, with no restart and no setting.
- ONE SPAN, TWO SINKS: every span opened live (`span()`, `start_span`)
  also opens a `jax.profiler.TraceAnnotation("dftpu.<name>")` on the same
  thread, so it lands in the host plane of the profiler's `.xplane.pb`
  on the device's clock. Spans recorded after the fact with explicit
  times (`finish_reserved`, `splice` of remote workers, `record_span`)
  stay in the store only. The store keeps `time.monotonic`.
- HOST-SIDE ONLY: spans wrap coordinator/worker *host* phases; nothing
  here may run inside a jax-traced function (tools/check_tracer_safety.py
  rule DFTPU109 enforces it), and the wire context must never enter a
  compile-cache key (span ids differ per task — keying on them would
  force one XLA trace per task; see plan/physical.py's cfg_items filter).
- BOUNDED: a ring buffer per query (oldest spans dropped once
  ``span_cap`` is hit, count surfaced as ``dropped``), LRU across queries
  with RUNNING queries pinned — identical retention contract to
  MetricsStore.stage_spans.
- DETERMINISTIC ENOUGH TO TEST: all timestamps are `time.monotonic`
  (one system-wide clock — comparable across processes on one host, the
  gRPC-localhost tier included); tests assert ordering, never wall-clock.

Cross-wire propagation: the coordinator attaches ``trace_ctx``
(`{"q": query_id, "parent": span_id}`) to the per-dispatch config dict of
the task envelope (runtime/coordinator.py `_dispatch_task`). A worker in
the coordinator's own process opens its phases (`worker_phase`:
``worker_decode``, ``worker_execute``, ``worker_output``) as LIVE spans on
whatever thread they run: the query's running trace is found on the
thread, or by ``q`` in `spans.running_tracer`'s process-wide registry
(a stage task runs under a pull's generator or on a deadline thread,
where no tracer is open), and the tracer goes on the thread's stack, so
that everything below records into the same trace: `execute_plan`'s
``prepare`` > ``h2d`` > ``input_wait`` (a consumer blocked on its
producer stage) and ``execute`` > ``gate_wait``, ``launch`` (the jitted
call up to its return), ``sync`` (the blocking read of a program's small
outputs, ONE a call of `execute_plan`: the flag vector and, where the
caller keeps metrics, every metric value and with them the output's row
count ride one `jax.device_get`; ``values`` says how many, ``syncs`` is
1. A second ``sync``, ``what=rows``, is left only where a stage output
stays on the device: the copying plane),
``program_lookup`` (the stage's shared-program slot), `host_view`'s
``d2h`` (a stage output's buffers and its row count in one pull:
``buffers``, ``round_trips`` 1, beside ``bytes``, ``rows``,
``capacity``), the ``regroup``. A worker behind a wire (the gRPC server marks
the context ``wire``) records its phases as plain JSON-able dicts
carrying that wire parent, and they ride the existing task-progress
payload back to be spliced into the query trace under the propagated
parent span, childless.

Exports: Chrome trace-event JSON (``to_chrome_trace`` — load the file in
Perfetto / chrome://tracing), a text profile report (``render_profile``,
folded into `explain_analyze`), and live aggregate counters
(`ObservabilityService.get_trace_summary`, console panel).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

# the recording half is a leaf module (`spans.py`), so that `ops/`, `io/`
# and `plan/` can open spans without importing the runtime layer; this
# module is its face for the runtime layer and everything above it
from datafusion_distributed_tpu import spans as _spans
from datafusion_distributed_tpu.spans import (  # noqa: F401
    DEFAULT_TRACE_STORE,
    NULL_TRACER,
    PROFILE_PREFIX,
    TRACE_CTX_KEY,
    TRACING_MODES,
    QueryTrace,
    Span,
    TraceStore,
    current,
    record_span,
    request_of,
    request_scope,
    resolve_tracing_mode,
    table_nbytes,
    tag_request,
    trace_call,
)


def worker_span(name: str, kind: str, t0: float, t1: float,
                wire_parent, **attrs) -> dict:
    """A worker-side span as a plain JSON-able dict: rides the existing
    task-progress payload back to the coordinator (in-process AND gRPC)
    where `Tracer.splice` adopts it under the propagated parent."""
    return {"name": name, "kind": kind, "t0": t0, "t1": t1,
            "wire_parent": wire_parent, "attrs": attrs}


# worker tasks in flight in this process (a ``worker_execute`` phase
# counts itself in and out), touched only under a live tracer
_tasks_running = [0]  # guarded-by: _tasks_lock
_tasks_lock = threading.Lock()


class worker_phase:
    """One worker-side phase (plan decode, task execute). In the
    coordinator's own process it is a live span whatever thread it runs
    on: a child of the thread's open span where the query's trace is open
    there, else (a pull's generator, `call_with_deadline`'s thread) the
    query's running trace is looked up by the ``q`` of ``tctx`` and the
    span opened under ``tctx["parent"]``. Either way the tracer is on the
    thread's stack, so `spans.current()` below it (`execute_plan`,
    `host_view`, a scan's `load`) records into the same trace, profiler
    annotations included. Where the context crossed a wire (``wire``,
    set by the gRPC server) or no such trace runs in this process: a
    `worker_span` dict appended to ``sink``, which rides the
    task-progress payload back to `Tracer.splice`. ``count_task``: carry
    ``running``, the worker tasks in flight in this process when this
    one began. ``tctx`` None: nothing at all, no lookup, no clock read."""

    __slots__ = ("_tctx", "_name", "_kind", "_sink", "_attrs", "_ctx",
                 "_t0", "_count_task")

    def __init__(self, tctx, name: str, kind: str, sink: list,
                 count_task: bool = False, **attrs):
        self._tctx = tctx
        self._name = name
        self._kind = kind
        self._sink = sink
        self._attrs = attrs
        self._ctx = None
        self._count_task = count_task

    def __enter__(self) -> "worker_phase":
        tctx = self._tctx
        if not tctx:
            return self
        tracer, parent = NULL_TRACER, None
        if not tctx.get("wire"):
            tracer = current()
            if not (tracer.active
                    and tracer.trace.query_id == tctx.get("q")):
                tracer = _spans.running_tracer(tctx.get("q"))
                parent = tctx.get("parent")
        if not tracer.active:
            self._t0 = time.monotonic()
            return self
        if self._count_task:
            with _tasks_lock:
                self._attrs["running"] = _tasks_running[0]
                _tasks_running[0] += 1
        self._ctx = tracer.span(self._name, self._kind, parent=parent,
                                **self._attrs)
        self._ctx.__enter__()
        return self

    def set(self, **attrs) -> None:
        if self._ctx is not None:
            self._ctx._span.set(**attrs)
        else:
            self._attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._ctx is not None:
            if self._count_task:
                with _tasks_lock:
                    _tasks_running[0] -= 1
            return self._ctx.__exit__(exc_type, exc, tb)
        if self._tctx and exc_type is None:
            self._sink.append(worker_span(
                self._name, self._kind, self._t0, time.monotonic(),
                self._tctx.get("parent"), **self._attrs,
            ))
        return False


# ---------------------------------------------------------------------------
# analysis helpers (tests + profile report)
# ---------------------------------------------------------------------------


def _interval_union(intervals) -> list:
    """Merge [lo, hi] intervals -> disjoint sorted list."""
    ivs = sorted((lo, hi) for lo, hi in intervals if hi > lo)
    out: list = []
    for lo, hi in ivs:
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def trace_coverage(trace: QueryTrace) -> tuple:
    """(covered_fraction, max_gap_fraction) of the ROOT span's interval by
    the union of every other span — the acceptance metric: >= 95% of the
    measured query wall attributed, no unattributed gap over 5%."""
    root = trace.root_span()
    if root is None or root.duration <= 0:
        return 0.0, 1.0
    lo, hi = root.t0, root.t1
    union = _interval_union(
        (max(s.t0, lo), min(s.t1, hi))
        for s in trace.span_list() if s.span_id != root.span_id
    )
    covered = sum(b - a for a, b in union)
    # gaps: before the first covered interval, between them, after the last
    gaps = []
    cursor = lo
    for a, b in union:
        gaps.append(a - cursor)
        cursor = b
    gaps.append(hi - cursor)
    dur = hi - lo
    return covered / dur, (max(gaps) if gaps else dur) / dur


def stage_data_rates(trace: QueryTrace) -> dict:
    """stage_id -> {"bytes", "wall_s", "bytes_per_s"}: every byte-carrying
    span (codec encode, dispatch ship, exchange transfer, worker staging)
    summed per stage lane and divided by the stage's EXECUTE wall (queue
    wait excluded) — the measured GB/s column the zero-copy roadmap item
    needs."""
    spans = trace.span_list()
    stage_spans = {
        s.attrs.get("stage"): s for s in spans if s.kind == "stage"
    }
    # children index: stage lane membership is transitive over parents
    by_id = {s.span_id: s for s in spans}

    def stage_of(s: Span):
        seen = 0
        cur = s
        while cur is not None and seen < 64:
            if cur.kind == "stage":
                return cur.attrs.get("stage")
            cur = by_id.get(cur.parent_id)
            seen += 1
        return None

    out: dict = {}
    for s in spans:
        b = s.attrs.get("bytes")
        if not b:
            continue
        sid = s.attrs.get("stage")
        if sid is None:
            sid = stage_of(s)
        if sid is None:
            continue
        slot = out.setdefault(sid, {"bytes": 0, "wall_s": 0.0})
        slot["bytes"] += int(b)
    for sid, slot in out.items():
        st = stage_spans.get(sid)
        wall = None
        if st is not None:
            wall = max(st.duration - float(st.attrs.get("queue_s", 0.0)),
                       0.0)
        slot["wall_s"] = wall if wall else 0.0
        slot["bytes_per_s"] = (
            slot["bytes"] / wall if wall else None
        )
    return out


def self_times(trace: QueryTrace) -> list:
    """[(span, self_seconds)] sorted descending: span duration minus the
    union of its direct children's intervals (overlapping children — a
    stage's concurrent tasks — must not subtract twice)."""
    spans = trace.span_list()
    children: dict = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)
    out = []
    for s in spans:
        kids = children.get(s.span_id, ())
        covered = sum(
            b - a for a, b in _interval_union(
                (max(k.t0, s.t0), min(k.t1, s.t1)) for k in kids
            )
        )
        out.append((s, max(s.duration - covered, 0.0)))
    out.sort(key=lambda p: -p[1])
    return out


def layer_report(store: Optional[TraceStore] = None) -> list:
    """One row a request, its finished traces merged (a request's
    `ctx.sql`, collect attempts, subqueries and result fetch are each a
    trace of their own under one ``request``; a trace with no request is
    its own row), oldest first:

    - ``t0_s``: when its first trace began (`time.monotonic`);
    - ``wall_s``: the summed wall of its traces' roots;
    - ``self_s``: `self_times` summed by span KIND (`parse`, `plan`,
      `attempt`, `prepare`, `execute`, `launch`, `sync`, `wait`,
      `worker`, `rpc`, `fetch`, `exchange`, `d2h`, ...): what each layer
      itself costs, children taken out, so that the kinds add up to
      ``wall_s`` where no two tasks overlap (the coordinator tier's
      worker threads do: there a kind is a sum over threads);
    - ``total_s``: whole durations summed by span NAME (`worker_execute`
      is a task inside its worker, its wait for its inputs included;
      `launch` the jitted calls up to their return);
    - ``counters``: ``bytes`` by span kind, ``transfers`` (buffers the
      fetch copied from a device), ``round_trips`` (times the fetch
      blocked on the device for them; both from the ``fetch`` span
      alone: a stage output's ``d2h`` carries ``buffers`` and
      ``round_trips`` of its own, which no counter sums), ``retries``
      (overflow retries, stamped on the root that succeeded),
      ``new_traces`` (programs traced afresh), ``masks`` (validity arrays
      a registration uploaded: one a column that holds a NULL), ``syncs``
      (blocking device-to-host
      reads of a program's small outputs, summed from the ``sync``
      spans: ONE span a program's call, so one a worker task, whose
      flags, metric values and row count ride one pull), ``tasks``
      (worker tasks run: one a ``worker_execute`` span, by which a sum
      over worker threads can be read a task),
      and every name of `spans.PROGRAM_COUNTERS` (what its programs
      counted while they were traced), zero included.

    The one report for operators (`render_profile` prints the last
    trace's row) and for the benchmark's program metrics."""
    return _layer_rows((store or DEFAULT_TRACE_STORE).finished_traces())


def _layer_rows(traces) -> list:
    # the span attributes summed into a request's ``counters``
    summed = ("new_traces", "masks", "syncs") + _spans.PROGRAM_COUNTERS
    # the result fetch's own: a stage output's ``d2h`` carries
    # ``round_trips`` too, and is no fetch
    of_fetch = ("transfers", "round_trips")
    rows: dict = {}
    for trace in traces:
        root = trace.root_span()
        if root is None:
            continue
        key = trace.request or trace.query_id
        row = rows.get(key)
        if row is None:
            row = rows[key] = {
                "request": key, "traces": [], "t0_s": trace.t0,
                "wall_s": 0.0,
                "self_s": {}, "total_s": {},
                "counters": {"bytes": {}, "retries": 0, "tasks": 0,
                             **dict.fromkeys(of_fetch + summed, 0)},
            }
        row["traces"].append(trace.query_id)
        row["wall_s"] += root.duration
        counters = row["counters"]
        counters["retries"] += int(root.attrs.get("retries", 0) or 0)
        for span, self_s in self_times(trace):
            row["self_s"][span.kind] = (
                row["self_s"].get(span.kind, 0.0) + self_s
            )
            row["total_s"][span.name] = (
                row["total_s"].get(span.name, 0.0) + span.duration
            )
            nbytes = span.attrs.get("bytes")
            if nbytes is not None:  # a declared 0 is reported as 0
                counters["bytes"][span.kind] = (
                    counters["bytes"].get(span.kind, 0) + int(nbytes)
                )
            names = summed + of_fetch if span.name == "fetch" else summed
            for name in names:
                counters[name] += int(span.attrs.get(name, 0) or 0)
            counters["tasks"] += span.name == "worker_execute"
    return list(rows.values())


def format_bytes(n: float) -> str:
    """Human-readable byte count (shared with console.py — one formatter,
    no drift between the panel and the profile report)."""
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024:
            return f"{n:.1f}{unit}"
        n /= 1024
    return f"{n:.1f}TiB"


_fmt_bytes = format_bytes


def render_profile(trace: QueryTrace, top_n: int = 10) -> str:
    """The per-query text profile (folded into explain_analyze): top-N
    spans by self time, per-stage data-plane bytes/sec, queue-wait vs
    execute split, fault events."""
    root = trace.root_span()
    spans = trace.span_list()
    if root is None or not spans:
        return ""
    lines = [f"-- trace profile (query {trace.query_id[:8]}) --"]
    cov, max_gap = trace_coverage(trace)
    lines.append(
        f"wall {root.duration:.4f}s  {len(spans)} spans"
        + (f" ({trace.dropped} dropped)" if trace.dropped else "")
        + f"  coverage {cov * 100.0:.1f}%"
        f"  max gap {max_gap * 100.0:.1f}%"
    )
    lines.append("top spans by self time:")
    for s, self_s in self_times(trace)[:top_n]:
        if self_s <= 0.0:
            continue
        where = []
        for k in ("stage", "task", "attempt", "worker"):
            v = s.attrs.get(k)
            if v is not None:
                where.append(f"{k}={v}")
        b = s.attrs.get("bytes")
        if b:
            where.append(_fmt_bytes(b))
        lines.append(
            f"  {self_s:8.4f}s  {s.kind:<9} {s.name:<18} "
            + " ".join(where)
        )
    rates = stage_data_rates(trace)
    if rates:
        lines.append("per-stage data plane:")
        for sid in sorted(rates, key=lambda x: (x is None, x)):
            slot = rates[sid]
            rate = slot.get("bytes_per_s")
            rate_txt = (
                f"{rate / 1e9:.3f} GB/s" if rate else "n/a"
            )
            lines.append(
                f"  stage {sid}: {_fmt_bytes(slot['bytes'])} "
                f"in {slot['wall_s']:.4f}s = {rate_txt}"
            )
    stage_spans = [s for s in spans if s.kind == "stage"]
    if stage_spans:
        queue = sum(float(s.attrs.get("queue_s", 0.0)) for s in stage_spans)
        execute = sum(s.duration for s in stage_spans) - queue
        lines.append(
            f"queue wait {queue:.4f}s vs execute {max(execute, 0.0):.4f}s "
            "(summed over stages)"
        )
    events = trace.event_list()
    if events:
        counts: dict = {}
        for _t, name, _a, _p in events:
            counts[name] = counts.get(name, 0) + 1
        lines.append(
            "events: " + ", ".join(
                f"{k}={counts[k]}" for k in sorted(counts)
            )
        )
    for row in _layer_rows([trace]):
        layers = sorted(row["self_s"].items(), key=lambda kv: -kv[1])
        lines.append(
            "layers (self time by span kind): " + "  ".join(
                f"{kind} {s:.4f}s" for kind, s in layers if s > 0.0
            )
        )
        c = row["counters"]
        lines.append(
            f"counters: bytes {_fmt_bytes(sum(c['bytes'].values()))}"
            f"  transfers {c['transfers']}"
            f"  round_trips {c['round_trips']}  retries {c['retries']}"
            f"  new_traces {c['new_traces']}"
            f"  syncs {c['syncs']}  tasks {c['tasks']}"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto / chrome://tracing)
# ---------------------------------------------------------------------------


def to_chrome_trace(trace: QueryTrace) -> dict:
    """Chrome trace-event JSON (the 'X' complete-event + 'i' instant-event
    subset Perfetto renders directly). Lanes (tids) group spans by stage /
    worker so the stage overlap and the data-plane hops read visually."""
    spans = trace.span_list()
    by_id = {s.span_id: s for s in spans}
    base = trace.t0
    lanes: dict = {}

    def lane_for(s: Span) -> str:
        if s.kind in ("query", "schedule", "plan"):
            return "coordinator"
        cur = s
        hops = 0
        while cur is not None and hops < 64:
            sid = cur.attrs.get("stage")
            if cur.kind == "stage" and sid is not None:
                return f"stage {sid}"
            cur = by_id.get(cur.parent_id)
            hops += 1
        return "coordinator"

    def tid_of(label: str) -> int:
        if label not in lanes:
            lanes[label] = len(lanes) + 1
        return lanes[label]

    events = []
    for s in spans:
        args = {k: v for k, v in s.attrs.items()}
        args["span_id"] = s.span_id
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        events.append({
            "name": s.name,
            "cat": s.kind,
            "ph": "X",
            "ts": round((s.t0 - base) * 1e6, 3),
            "dur": round(s.duration * 1e6, 3),
            "pid": 1,
            "tid": tid_of(lane_for(s)),
            "args": args,
        })
    for t, name, attrs, parent in trace.event_list():
        parent_span = by_id.get(parent)
        lane = lane_for(parent_span) if parent_span else "coordinator"
        events.append({
            "name": name, "cat": "event", "ph": "i", "s": "t",
            "ts": round((t - base) * 1e6, 3),
            "pid": 1, "tid": tid_of(lane), "args": dict(attrs),
        })
    for label, tid in sorted(lanes.items(), key=lambda kv: kv[1]):
        events.append({
            "name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
            "args": {"name": label},
        })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "query_id": trace.query_id,
            "spans_dropped": trace.dropped,
        },
    }
