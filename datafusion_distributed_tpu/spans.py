"""Spans: the recording half of distributed query tracing.

A leaf module (standard library and `jax.profiler` only), so that every
layer may open a span where its boundary is: `ops/` (`Table.to_pandas`'s
``fetch``, `host_view`'s ``d2h``), `io/` (`table_to_arrow`), `plan/`
(`execute_plan`'s ``prepare`` and ``execute``) as well as `sql/` and
`runtime/`. It holds the span tree's store (`Span`, `QueryTrace`,
`Tracer`, `TraceStore`, the process-wide `DEFAULT_TRACE_STORE`), the no-op
`NULL_TRACER`, the mode switch (`resolve_tracing_mode`), the thread's
open tracer and request (`current`, `request_scope`) and the traces of the
tiers that have no coordinator (`trace_call`, `fetch_call`,
`record_span`). What reads a trace (self times, `layer_report`, the text
profile, the Chrome export) and the worker's side of the wire live in
`runtime/tracing.py`, which also states the design constraints and
re-exports these names for the runtime layer.
"""

from __future__ import annotations

import threading
import time
import uuid
import weakref
import zlib
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation as _Annotation

#: `SET distributed.tracing` modes (validated at SET time, sql/context.py)
TRACING_MODES = ("off", "on", "sampled")

#: config key the trace context rides under in the task envelope. MUST
#: stay out of every compile-cache key (plan/physical.py filters it from
#: cfg_items; runtime/worker.py strips it before execute_plan) — span ids
#: differ per task and would otherwise fragment the program caches into
#: one XLA trace per task.
TRACE_CTX_KEY = "trace_ctx"

#: every live span's name in the profiler's own trace is this prefix plus
#: the span's name (`dftpu.execute`, `dftpu.d2h`, ...): one pattern for a
#: trace reducer to keep. Fixed; PERF.md section 3 lists the names.
PROFILE_PREFIX = "dftpu."

#: what a program counts while it is traced (`ExecContext.count`), kept
#: with its cached executable and set, every name, on the `execute` /
#: `mesh.execute` span of each call; `layer_report()` sums every name into
#: a request's ``counters``. A new counter is a name here and one
#: ``ctx.count(name)``. ``masked_filters``: filters that handed an
#: aggregate their mask and did not compact; ``direct_groupings``:
#: aggregates that addressed their groups by dictionary codes and built
#: no group table; ``dense_aggregates``: aggregates, grouped or global,
#: whose reductions ran as dense masked passes over a small known domain
#: and not as scatters; ``group_slots``: the slots of the largest group
#: table the program built (the claim loop's) or addressed (a direct
#: grouping's domain; 1 for a global aggregate), `ExecContext.count_largest`;
#: ``scatter_reductions``: the per-slot reductions, and a direct
#: grouping's slot-presence pass, that lowered as scatters;
#: ``presence_from_count``: direct groupings past the dense cut whose used
#: slots were read off the COUNT(*) they reduce, with no presence scatter;
#: ``fetch_bounded_sorts``: sorts under a static fetch whose output is the
#: fetch rounded up and not their input's capacity (`ops/sort.py
#: fetch_capacity`);
#: ``partitioned_scans``: scans whose task inputs are a partitioned table's
#: partitions used where they lie, as the mesh's shards
#: (`runtime/mesh_executor.py place_partitions`); ``mesh_exchange_bytes``:
#: the bytes the exchanges' collectives carry (``all_to_all``,
#: ``all_gather``, ``pmax``, ``ppermute``), each one's operand summed over
#: the chips of its axis, from the planned buffers' shapes
#: (`parallel/exchange.py collective_tally`).
PROGRAM_COUNTERS = ("masked_filters", "direct_groupings", "dense_aggregates",
                    "group_slots", "scatter_reductions", "presence_from_count",
                    "partitioned_scans", "mesh_exchange_bytes",
                    "fetch_bounded_sorts")

_SPAN_CAP = 4096     # ring-buffer bound per query
_EVENT_CAP = 2048    # trace-level event bound per query
_QUERY_CAP = 32      # LRU bound across queries (running ones pinned)


def table_nbytes(table) -> int:
    """Host-side device-buffer byte count of an ops Table: data + validity
    of every column (no device sync — `.nbytes` reads the aval). The
    data-plane attribution unit: in-process shipments move exactly these
    buffers (by reference), the wire transport serializes them (plus codec
    framing), so spans attributed with this match `nbytes` by
    construction."""
    total = 0
    for c in getattr(table, "columns", ()):
        data = getattr(c, "data", None)
        if data is not None:
            total += int(data.nbytes)
        validity = getattr(c, "validity", None)
        if validity is not None:
            total += int(validity.nbytes)
    return total


def resolve_tracing_mode(options: Optional[dict]) -> str:
    """The effective tracing mode of one query: `SET distributed.tracing`
    from a config-options dict (unknown/missing -> off), or "on" while a
    `jax.profiler` session is recording. Read once a query, here."""
    mode = str((options or {}).get("tracing", "off") or "off").strip().lower()
    if mode != "on" and _Annotation.is_enabled():
        return "on"
    return mode if mode in TRACING_MODES else "off"


# the thread's open tracers (innermost last) and its request: what lets
# code far below a query's entry point (execute_plan, host_view, a scan's
# load, table_to_arrow) find the trace it belongs to without plumbing
_LOCAL = threading.local()


def current():
    """The tracer of the innermost span open on this thread, else
    NULL_TRACER."""
    st = getattr(_LOCAL, "tracers", None)
    return st[-1] if st else NULL_TRACER


class request_scope:
    """Every trace begun on this thread inside the scope carries
    ``request=<id>`` (and ``attrs``) on its root: a request's retries
    re-enter `Coordinator.execute`, and scalar subqueries run programs at
    plan time, each a trace of its own. ``request`` None: the first trace
    begun inside mints the identifier (`TraceStore.begin`), so a request
    that is never traced never pays for one; read it back from
    ``.request`` (None still, where nothing was traced)."""

    __slots__ = ("request", "attrs", "_saved")

    def __init__(self, request: Optional[str] = None, **attrs):
        self.request = request
        self.attrs = attrs

    def __enter__(self) -> "request_scope":
        self._saved = getattr(_LOCAL, "request", None)
        _LOCAL.request = self
        return self

    def __exit__(self, *exc) -> bool:
        _LOCAL.request = self._saved
        return False


# the traces running in this process, by query id: what lets a worker
# phase on a thread that holds no tracer (a pull's generator, a deadline
# thread, another coordinator's store) join its query's trace
# (`running_tracer`). `TraceStore.begin` fills it and `finish` empties it
# (one assignment, pop or get each, so no lock of its own); a trace that
# is begun and never finished goes with its Tracer.
_RUNNING: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def running_tracer(query_id):
    """The live Tracer of a query whose trace is running in THIS process
    (whichever store began it), else NULL_TRACER."""
    return _RUNNING.get(query_id, NULL_TRACER)


def _sampled(query_id: str, rate: float) -> bool:
    """Deterministic per-query sampling decision: a hash of the query id
    against ``rate`` — the same query id always decides the same way, so a
    replayed run re-traces the same queries."""
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return (zlib.crc32(query_id.encode()) / 0xFFFFFFFF) < rate


class Span:
    """One closed span. ``t0``/``t1`` are raw `time.monotonic` seconds;
    exports normalize against the trace origin."""

    __slots__ = ("span_id", "parent_id", "name", "kind", "t0", "t1",
                 "attrs", "_annotation")

    def __init__(self, span_id: int, parent_id: Optional[int], name: str,
                 kind: str, t0: float, t1: float = 0.0,
                 attrs: Optional[dict] = None):
        self._annotation = None  # the live span's profiler annotation
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs if attrs is not None else {}

    @property
    def duration(self) -> float:
        return max(self.t1 - self.t0, 0.0)

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self


class _NullSpan:
    """The span NULL_TRACER hands out: swallows every mutation."""

    __slots__ = ()
    span_id = None
    parent_id = None
    attrs: dict = {}
    t0 = t1 = 0.0
    duration = 0.0

    def set(self, **attrs) -> "_NullSpan":
        return self


_A_NULL_SPAN = _NullSpan()


class _NullCtx:
    """Reusable no-op context manager yielding the null span."""

    __slots__ = ()

    def __enter__(self) -> _NullSpan:
        return _A_NULL_SPAN

    def __exit__(self, *exc) -> bool:
        return False


_A_NULL_CTX = _NullCtx()


class _NullTracer:
    """The off-mode tracer: every method is a constant-time no-op — call
    sites keep one unconditional code path and pay ~nothing when tracing
    is off (the "always cheap when off" contract)."""

    __slots__ = ()
    active = False
    trace = None

    def span(self, name, kind, parent=None, **attrs):
        return _A_NULL_CTX

    def start_span(self, name, kind, parent=None, **attrs):
        return _A_NULL_SPAN

    def end_span(self, span) -> None:
        pass

    def open_root(self, name, kind, **attrs):
        return _A_NULL_SPAN

    def event(self, name, **attrs) -> None:
        pass

    def reserved_id(self, key):
        return None

    def finish_reserved(self, key, name, kind, t0, t1, parent=None,
                        **attrs) -> None:
        pass

    def current_id(self):
        return None

    def wire_ctx(self):
        return None

    def splice(self, span_dicts, default_parent=None) -> None:
        pass


NULL_TRACER = _NullTracer()


class QueryTrace:
    """One query's bounded span/event store. Thread-safe: spans land from
    the coordinator's stage/task fan-out threads and (spliced) worker
    payloads concurrently."""

    def __init__(self, query_id: str, span_cap: int = _SPAN_CAP,
                 event_cap: int = _EVENT_CAP):
        self.query_id = query_id
        # the request this trace belongs to and what its root carries
        # besides (`request_scope`); `layer_report` merges by it
        self.request: Optional[str] = None
        self.root_attrs: dict = {}
        self.t0 = time.monotonic()
        self.t1: Optional[float] = None
        self.finished = False
        # ring buffers: deque(maxlen=...) drops the OLDEST on overflow;
        # `dropped` counts evictions so exports can say "N spans dropped"
        self.spans: deque = deque(maxlen=span_cap)  # guarded-by: _lock
        self.events: deque = deque(maxlen=event_cap)  # guarded-by: _lock
        self.dropped = 0  # guarded-by: _lock
        self.events_dropped = 0  # guarded-by: _lock
        self._lock = threading.Lock()
        self._next_id = 0  # guarded-by: _lock
        self._reserved: dict = {}  # guarded-by: _lock
        self.root_id: Optional[int] = None
        # summary tally memo, filled by TraceStore._tally once finished
        self._tally_cache: Optional[tuple] = None

    # -- id allocation ------------------------------------------------------
    def new_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return self._next_id

    def reserve(self, key) -> int:
        """Pre-allocate a span id for ``key`` (e.g. ``("stage", 3)``) so
        children created BEFORE the span closes (task spans inside a still
        -running stage) can parent under it; `finish_reserved` later
        appends the span with this id."""
        with self._lock:
            sid = self._reserved.get(key)
            if sid is None:
                self._next_id += 1
                sid = self._reserved[key] = self._next_id
            return sid

    # -- recording ----------------------------------------------------------
    def add_span(self, span: Span) -> None:
        with self._lock:
            if len(self.spans) == self.spans.maxlen:
                self.dropped += 1
            self.spans.append(span)

    def add_event(self, t: float, name: str, attrs: dict,
                  parent: Optional[int]) -> None:
        with self._lock:
            if len(self.events) == self.events.maxlen:
                self.events_dropped += 1
            self.events.append((t, name, attrs, parent))

    # -- inspection ---------------------------------------------------------
    def span_list(self) -> list:
        with self._lock:
            return list(self.spans)

    def event_list(self) -> list:
        with self._lock:
            return list(self.events)

    def root_span(self) -> Optional[Span]:
        rid = self.root_id
        if rid is None:
            return None
        for s in self.span_list():
            if s.span_id == rid:
                return s
        return None

    def finish(self) -> None:
        self.finished = True
        if self.t1 is None:
            self.t1 = time.monotonic()


class Tracer:
    """Per-query recording facade over a QueryTrace. Implicit parenting
    rides a PER-THREAD span stack (`span()` pushes/pops), so nested host
    phases need no explicit plumbing; work fanned out to pool threads
    passes an explicit ``parent`` (usually a reserved stage span id) to
    seed its own stack."""

    __slots__ = ("trace", "_local", "__weakref__")
    active = True

    def __init__(self, trace: QueryTrace):
        self.trace = trace
        self._local = threading.local()

    # -- parent stack -------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def current_id(self) -> Optional[int]:
        st = self._stack()
        return st[-1] if st else self.trace.root_id

    # -- spans --------------------------------------------------------------
    def span(self, name: str, kind: str, parent: Optional[int] = None,
             **attrs):
        """Context manager: opens a span now, closes+records it on exit.
        An exception closing the span is recorded as ``error=<TypeName>``
        and re-raised."""
        return _SpanCtx(self, name, kind, parent, attrs)

    def start_span(self, name: str, kind: str,
                   parent: Optional[int] = None, **attrs) -> Span:
        """Explicit begin (no stack participation) — for spans whose end
        lives in a different scope (the query root)."""
        pid = parent if parent is not None else self.current_id()
        sp = Span(self.trace.new_id(), pid, name, kind,
                  time.monotonic(), attrs=attrs)
        sp._annotation = _open_annotation(name)
        return sp

    def end_span(self, span: Span) -> None:
        span.t1 = time.monotonic()
        _close_annotation(span)
        self.trace.add_span(span)

    def open_root(self, name: str, kind: str, **attrs) -> Span:
        """Begin the trace's root span (ended by `end_span`), carrying the
        request's identifier and the `request_scope`'s attributes."""
        trace = self.trace
        if trace.request is not None:
            attrs["request"] = trace.request
        attrs.update(trace.root_attrs)
        root = self.start_span(name, kind, **attrs)
        trace.root_id = root.span_id
        return root

    def reserved_id(self, key) -> int:
        return self.trace.reserve(key)

    def finish_reserved(self, key, name: str, kind: str, t0: float,
                        t1: float, parent: Optional[int] = None,
                        **attrs) -> None:
        """Record the span pre-allocated by `reserved_id(key)` with
        explicit timestamps (the stage spans: the scheduler knows
        submit/start/end after the fact). Default parent: the recording
        thread's current span (the scheduler span), else the root."""
        sid = self.trace.reserve(key)
        pid = parent if parent is not None else self.current_id()
        self.trace.add_span(Span(sid, pid, name, kind, t0, t1, attrs))

    # -- events -------------------------------------------------------------
    def event(self, name: str, **attrs) -> None:
        self.trace.add_event(time.monotonic(), name, attrs,
                             self.current_id())

    # -- cross-wire ---------------------------------------------------------
    def wire_ctx(self) -> dict:
        """The context that rides the task envelope: worker-side spans
        recorded under it join the trace at `splice` time via the
        propagated parent span id."""
        return {"q": self.trace.query_id, "parent": self.current_id()}

    def splice(self, span_dicts, default_parent: Optional[int] = None
               ) -> None:
        """Adopt worker-side span dicts (see worker_span) into this trace:
        each gets a fresh local id and parents under its propagated
        ``wire_parent`` (falling back to ``default_parent`` / the root).
        Worker timestamps are CLOCK_MONOTONIC — system-wide on Linux, so
        same-host workers (in-process and gRPC-localhost tiers) splice
        without rebasing."""
        if default_parent is None:
            default_parent = self.current_id()
        for d in span_dicts:
            try:
                pid = d.get("wire_parent")
                if pid is None:
                    pid = default_parent
                attrs = dict(d.get("attrs") or {})
                attrs.setdefault("remote", True)
                self.trace.add_span(Span(
                    self.trace.new_id(), pid,
                    str(d.get("name", "worker")),
                    str(d.get("kind", "execute")),
                    float(d.get("t0", 0.0)), float(d.get("t1", 0.0)),
                    attrs,
                ))
            except (TypeError, ValueError, KeyError):
                continue  # a malformed wire span must never fail the task


class _SpanCtx:
    __slots__ = ("_tracer", "_span", "_name", "_kind", "_parent", "_attrs")

    def __init__(self, tracer: Tracer, name, kind, parent, attrs):
        self._tracer = tracer
        self._name = name
        self._kind = kind
        self._parent = parent
        self._attrs = attrs
        self._span: Optional[Span] = None

    def __enter__(self) -> Span:
        tr = self._tracer
        pid = self._parent if self._parent is not None else tr.current_id()
        sp = Span(tr.trace.new_id(), pid, self._name, self._kind,
                  time.monotonic(), attrs=self._attrs)
        tr._stack().append(sp.span_id)
        tracers = getattr(_LOCAL, "tracers", None)
        if tracers is None:
            tracers = _LOCAL.tracers = []
        tracers.append(tr)
        sp._annotation = _open_annotation(self._name)
        self._span = sp
        return sp

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self._span
        tr = self._tracer
        st = tr._stack()
        if st and st[-1] == sp.span_id:
            st.pop()
        elif sp.span_id in st:  # defensive: unwound out of order
            st.remove(sp.span_id)
        if exc_type is not None:
            sp.attrs.setdefault("error", exc_type.__name__)
        sp.t1 = time.monotonic()
        _close_annotation(sp)
        tracers = _LOCAL.tracers
        if tracers and tracers[-1] is tr:
            tracers.pop()
        elif tr in tracers:  # defensive: unwound out of order
            tracers.remove(tr)
        tr.trace.add_span(sp)
        return False


def _open_annotation(name: str):
    """The span's second sink: the same interval, in the profiler's own
    trace (a no-op object while no session records)."""
    annotation = _Annotation(PROFILE_PREFIX + name)
    annotation.__enter__()
    return annotation


def _close_annotation(span: Span) -> None:
    annotation = span._annotation
    if annotation is not None:
        span._annotation = None
        annotation.__exit__(None, None, None)


class TraceStore:
    """query_id -> QueryTrace, LRU-bounded with running queries pinned
    (the MetricsStore retention contract). One process-wide default store
    (`DEFAULT_TRACE_STORE`) backs `ctx.last_trace()`,
    `QueryHandle.trace()`, explain_analyze's profile fold and the
    observability summary."""

    def __init__(self, query_cap: int = _QUERY_CAP,
                 span_cap: int = _SPAN_CAP):
        self.query_cap = query_cap
        self.span_cap = span_cap
        # insertion order == LRU order
        self._traces: dict = {}  # guarded-by: _lock; per-query: swept-by finish
        self._running: set = set()  # guarded-by: _lock
        self._lock = threading.Lock()
        self._started_total = 0  # guarded-by: _lock

    # -- lifecycle ----------------------------------------------------------
    def begin(self, query_id: str, mode: str,
              sample_rate: float = 0.125):
        """-> a live Tracer for this query, or NULL_TRACER when the mode
        (or the sampling decision) says no. The trace is pinned against
        LRU eviction until `finish(query_id)`, and belongs to the
        request of the thread's `request_scope`."""
        if mode == "off":
            return NULL_TRACER
        if mode == "sampled" and not _sampled(query_id, sample_rate):
            return NULL_TRACER
        trace = QueryTrace(query_id, span_cap=self.span_cap)
        scope = getattr(_LOCAL, "request", None)
        if scope is not None:
            if scope.request is None:
                scope.request = uuid.uuid4().hex
            trace.request, trace.root_attrs = scope.request, scope.attrs
        with self._lock:
            self._running.add(query_id)
            self._traces[query_id] = trace
            self._started_total += 1
            self._evict_locked()
        tracer = _RUNNING[query_id] = Tracer(trace)
        return tracer

    def finish(self, query_id: str) -> None:
        with self._lock:
            self._running.discard(query_id)
            trace = self._traces.get(query_id)
            self._evict_locked()
        if trace is not None:
            if running_tracer(query_id).trace is trace:
                _RUNNING.pop(query_id, None)
            trace.finish()

    def _evict_locked(self) -> None:
        if len(self._traces) <= self.query_cap:
            return
        for qid in list(self._traces):
            if len(self._traces) <= self.query_cap:
                break
            if qid in self._running:
                continue  # never evict a live query's trace
            self._traces.pop(qid)

    # -- lookup -------------------------------------------------------------
    def get(self, query_id: str) -> Optional[QueryTrace]:
        with self._lock:
            trace = self._traces.get(query_id)
            if trace is not None:  # move-to-end: LRU touch
                self._traces.pop(query_id)
                self._traces[query_id] = trace
            return trace

    def finished_traces(self) -> list:
        """Every retained FINISHED trace, oldest first."""
        with self._lock:
            finished = [t for t in self._traces.values() if t.finished]
        return sorted(finished, key=lambda t: t.t0)

    def last(self) -> Optional[QueryTrace]:
        """Most recently FINISHED trace (running ones are still filling)."""
        finished = self.finished_traces()
        if not finished:
            return None
        return max(finished, key=lambda t: t.t1 or 0.0)

    def clear(self) -> None:
        """Drop every finished trace (a reader that wants one window's
        requests clears before it; running traces stay pinned)."""
        with self._lock:
            for qid in [q for q in self._traces
                        if q not in self._running]:
                self._traces.pop(qid)

    def annotate(self, query_id: str, **attrs) -> bool:
        """Attach attrs to a trace's root span after the fact (what is
        known only once the execute returned: the overflow-retry count,
        the serving handle's id)."""
        trace = self.get(query_id)
        if trace is None:
            return False
        root = trace.root_span()
        if root is not None:
            root.attrs.update(attrs)
        return True

    # -- aggregate counters (observability surface) -------------------------
    @staticmethod
    def _tally(trace: QueryTrace) -> tuple:
        """(spans_by_kind, events_by_name, bytes, dropped) for one trace.
        Cached once the trace is FINISHED — its spans/events are immutable
        from then on (post-finish `annotate` only touches root attrs, not
        counts), so the console polling the summary twice a second scans
        only the handful of running traces, not every retained one."""
        cached = getattr(trace, "_tally_cache", None)
        if cached is not None:
            return cached
        by_kind: dict = {}
        by_name: dict = {}
        nbytes = 0
        for s in trace.span_list():
            by_kind[s.kind] = by_kind.get(s.kind, 0) + 1
            b = s.attrs.get("bytes")
            if b:
                nbytes += int(b)
        for _t, name, _a, _p in trace.event_list():
            by_name[name] = by_name.get(name, 0) + 1
        out = (by_kind, by_name, nbytes, trace.dropped)
        if trace.finished:
            trace._tally_cache = out
        return out

    def summary(self) -> dict:
        with self._lock:
            traces = list(self._traces.values())
            running = len(self._running)
            started = self._started_total
        spans_by_kind: dict = {}
        events_by_name: dict = {}
        total_bytes = 0
        dropped = 0
        for t in traces:
            by_kind, by_name, nbytes, t_dropped = self._tally(t)
            dropped += t_dropped
            for k, n in by_kind.items():
                spans_by_kind[k] = spans_by_kind.get(k, 0) + n
            for k, n in by_name.items():
                events_by_name[k] = events_by_name.get(k, 0) + n
            total_bytes += nbytes
        return {
            "traces": len(traces),
            "traces_started": started,
            "running": running,
            "spans": sum(spans_by_kind.values()),
            "spans_by_kind": spans_by_kind,
            "spans_dropped": dropped,
            "events": sum(events_by_name.values()),
            "events_by_name": events_by_name,
            "data_plane_bytes": total_bytes,
        }


DEFAULT_TRACE_STORE = TraceStore()


# ---------------------------------------------------------------------------
# traces of the tiers that have no coordinator: one host call, one trace
# ---------------------------------------------------------------------------


def _sample_rate(options: Optional[dict]) -> float:
    try:
        return float((options or {}).get("tracing_sample_rate", 0.125))
    except (TypeError, ValueError):
        return 0.125


class _CallTrace:
    """Context manager of `trace_call` where the call is traced.
    ``tracer`` is the live Tracer, ``span`` the span that covers the call,
    ``request`` the identifier its trace carries."""

    __slots__ = ("tracer", "span", "request", "_name", "_mode", "_attrs",
                 "_store", "_options", "_ctx", "_scope")

    def __init__(self, name, mode, tracer, options, request, store, attrs):
        self._name = name
        self._mode = mode
        self._options = options
        self._store = store
        self._attrs = attrs
        self.request = request
        self.tracer = tracer  # the trace open on this thread, if any
        self.span = _A_NULL_SPAN
        self._ctx = _A_NULL_CTX
        self._scope = None  # set where this call began a trace of its own

    def __enter__(self) -> "_CallTrace":
        tracer = self.tracer
        if tracer.active:
            # a child span of the trace already open on this thread (a
            # scalar subquery at plan time, `ctx.sql` under a serving
            # submit): it belongs to that trace's request
            if self.request is None:
                self.request = tracer.trace.request
        else:
            scope = request_scope(self.request)
            scope.__enter__()
            tracer = self.tracer = self._store.begin(
                uuid.uuid4().hex, self._mode,
                sample_rate=_sample_rate(self._options),
            )
            if not tracer.active:  # sampled out
                scope.__exit__(None, None, None)
                self.request = None
                return self
            self._scope = scope
            self.request = self._attrs["request"] = scope.request
        # either way on the thread's stacks, so `current()` finds it from
        # anywhere below the call
        self._ctx = tracer.span(self._name, self._name, **self._attrs)
        self.span = self._ctx.__enter__()
        if self._scope is not None:
            tracer.trace.root_id = self.span.span_id
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ctx.__exit__(exc_type, exc, tb)
        if self._scope is not None:
            self._scope.__exit__(exc_type, exc, tb)
            self._store.finish(self.tracer.trace.query_id)
        return False


class _CallOff:
    """What `trace_call` hands out with tracing off: one shared no-op."""

    __slots__ = ()
    tracer = NULL_TRACER
    span = _A_NULL_SPAN
    request = None

    def __enter__(self) -> "_CallOff":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_A_CALL_OFF = _CallOff()


def trace_call(name: str, options: Optional[dict] = None,
               request: Optional[str] = None, store=None, **attrs):
    """Trace one host call of a tier that has no coordinator (`ctx.sql`,
    a direct or mesh collect, a serving submit, a result fetch): a trace
    of its own, begun and finished inside the call, its root named
    ``name`` and carrying ``request`` (minted where None); or, where a
    trace is already open on this thread, a child span of it. Off (no
    SET, no profiler session): one mode lookup, and a shared no-op whose
    ``request`` is None."""
    tracer = current()
    mode = "on" if tracer.active else resolve_tracing_mode(options)
    if mode == "off":
        return _A_CALL_OFF
    return _CallTrace(name, mode, tracer, options, request,
                      store or DEFAULT_TRACE_STORE, attrs)


def tag_request(table, request: Optional[str]):
    """Let a traced collect's result carry its request's identifier as a
    plain Python attribute outside the pytree (jit, `tree_map` and the
    data plane never see it; `jax.block_until_ready` hands the same
    object back), so that `Table.to_pandas()` / `table_to_arrow(table)`
    on the bare Table trace their fetch under the same request. The
    caller tags a Table object of its own making, never one that a store
    or a cache also holds."""
    if request is not None:
        table._request = request
    return table


def request_of(table) -> Optional[str]:
    return getattr(table, "_request", None)


def fetch_call(table):
    """`trace_call("fetch")` for the host materialization of a result
    Table, which knows no session: traced where its collect was (the
    Table then carries the request) or while a profiler session records
    (a bare Table's fetch then stands alone)."""
    request = request_of(table)
    return trace_call(
        "fetch", {"tracing": "on"} if request is not None else None,
        request,
    )


def record_span(name: str, t0: float, t1: float,
                options: Optional[dict] = None,
                request: Optional[str] = None, store=None,
                **attrs) -> Optional[str]:
    """A whole trace of one span known only after the fact (explicit
    `time.monotonic` times; store only): the serving tier's submit-to-
    admit wait, which begins on the client's thread and ends on the
    driver's. -> the request the trace carries (minted where None was
    given), None where nothing was recorded."""
    mode = resolve_tracing_mode(options)
    if mode == "off":
        return None
    store = store or DEFAULT_TRACE_STORE
    query_id = uuid.uuid4().hex
    with request_scope(request):
        tracer = store.begin(query_id, mode,
                             sample_rate=_sample_rate(options))
    if not tracer.active:
        return None
    trace = tracer.trace
    trace.t0 = t0
    root = Span(trace.new_id(), None, name, name, t0, t1,
                {"request": trace.request, **attrs})
    trace.root_id = root.span_id
    trace.add_span(root)
    store.finish(query_id)
    return trace.request
