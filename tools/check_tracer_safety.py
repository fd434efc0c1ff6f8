#!/usr/bin/env python
"""Tracer-safety + determinism lint for datafusion_distributed_tpu.

A custom AST lint (pure stdlib — no jax import, no device, no network) for
the failure modes generic linters cannot see because they are about WHEN
code runs, not what it says:

- code inside a traced/jitted function executes ONCE at trace time with
  abstract Tracer values: ``float()``/``int()``/``bool()`` on a traced
  value raises (or worse, silently bakes a trace-time constant), Python
  ``if`` on a tracer raises ConcretizationTypeError, ``np.*`` on a tracer
  either errors or silently falls back to host constants, and
  ``time``/``random`` calls bake one trace's value into every later
  execution of the compiled program.
- the engine guarantees byte-identical results between single-node and
  distributed execution. Iterating an UNORDERED collection (``set``/
  ``frozenset``) in codec / fingerprint / planner paths makes plan bytes,
  fingerprints or plan shapes depend on hash-seed iteration order —
  "wrong results, no error" across processes.
- mutable default arguments alias one instance across calls — in a
  long-lived worker process that is cross-query state leakage.

Rule codes (DFTPU1xx; the DFTPU0xx range is the plan verifier's,
plan/verify.py):

  DFTPU101  tracer-coercion      float()/int()/bool() in a trace path
  DFTPU102  tracer-branch        if/while/assert on a jnp/lax expression
  DFTPU103  np-in-trace          np.* call in a trace path
  DFTPU104  unordered-iteration  iterating a set/frozenset expression
  DFTPU105  time-random-in-trace time.*/random.* call in a trace path
                                 (EXCEPT the monotonic clocks —
                                 time.monotonic/perf_counter[_ns] report
                                 as DFTPU109, the tracing-span rule)
  DFTPU106  mutable-default      def f(x=[] / {} / set())
  DFTPU109  span-in-trace        tracing-span API / profiler
                                 TraceAnnotation / time.monotonic /
                                 time.perf_counter call in a trace path
                                 (distributed-tracing instrumentation is
                                 host-side only: a span opened inside a
                                 jitted function would record trace-time
                                 once and bake its clock reads into the
                                 compiled program). Takes precedence
                                 over DFTPU105 for the monotonic clocks
                                 — allowlist entries must name DFTPU109
  DFTPU110  telemetry-in-trace   telemetry / event-log API call
                                 (runtime/telemetry.py metric mutation,
                                 registry snapshot, runtime/eventlog.py
                                 log_event) in a trace path — metrics
                                 and structured events are host-side
                                 only: inside a jitted function the
                                 call runs ONCE at trace time (one
                                 phantom increment/event baked per
                                 compile, nothing per execution), and a
                                 Tracer argument in a field errors

"Trace path" = a function that executes under jax tracing: ``_execute``
and ``evaluate`` methods in the plan/ops/parallel layers, any function
passed to jit/shard_map/cond/while_loop/fori_loop/scan, nested functions
defined inside those, and (transitively, within one module) functions
they call.

Intentional exceptions live in tools/tracer_safety_allowlist.txt as
``path::RULE::qualname  # one-line justification``; the gate fails on any
finding not covered there AND on any stale allowlist entry (an entry
matching no finding is dead weight that can mask a future regression
under the same key — tools/lint_common.py, shared with the concurrency
gate). Exit code 0 = clean, 1 = violations/stale entries, 2 = usage
error.

Usage:
  python tools/check_tracer_safety.py                # lint the package
  python tools/check_tracer_safety.py FILE [FILE..]  # lint specific files
  python tools/check_tracer_safety.py --json         # machine-readable
  python tools/check_tracer_safety.py --allowlist F  # alternate allowlist
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lint_common import (  # noqa: E402
    Finding,
    apply_allowlist,
    load_allowlist,
    report_text,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "datafusion_distributed_tpu"
DEFAULT_ALLOWLIST = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "tracer_safety_allowlist.txt"
)

#: method names that ARE trace paths in these layers (operators trace their
#: whole pipeline; expressions evaluate inside the traced program)
TRACE_METHOD_NAMES = {"_execute", "evaluate", "_execute_mesh_arm"}
#: kernel entry points called (cross-module) from _execute during tracing —
#: the per-module call-graph closure cannot see those edges, so they seed
#: explicitly; same-module helpers they call are then traced transitively
TRACE_SEED_NAMES = {
    "hash_aggregate", "global_aggregate", "hash_join", "build_join_table",
    "sort_table", "limit_table", "window_compute", "shuffle_exchange",
    "range_shuffle_exchange", "coalesce_exchange", "broadcast_exchange",
    "group_coalesce_exchange", "expr_to_column", "concat_tables",
    "hash_columns",
}
#: directories (package-relative) whose TRACE_METHOD_NAMES methods trace
TRACE_DIRS = ("ops", "plan", "parallel")
#: extra module files containing traced closures outside those directories
TRACE_FILES = ("runtime/mesh_executor.py", "runtime/mesh_worker.py")
#: calls whose function-valued arguments become traced code
TRACING_CALLS = {
    "jit", "shard_map", "_shard_map", "cond", "while_loop", "fori_loop",
    "scan", "vmap", "pmap", "checkpoint", "switch",
}
#: np.* members that construct static scalars / dtype metadata — standard
#: and safe at trace time (np.uint32(7) is a constant, not host compute)
NP_STATIC_MEMBERS = {
    "uint8", "uint16", "uint32", "uint64", "int8", "int16", "int32",
    "int64", "float16", "float32", "float64", "bool_", "dtype", "iinfo",
    "finfo", "promote_types", "result_type", "issubdtype",
}
#: jnp/lax calls that inspect dtype METADATA (static), not traced values —
#: Python branching on these is fine
TRACED_STATIC_CALLS = {
    "issubdtype", "dtype", "result_type", "promote_types", "iinfo", "finfo",
}
#: argument shapes considered static (host values) for DFTPU101
STATIC_CALLS = {"len", "round_up_pow2", "ord"}
STATIC_ATTRS = {
    "shape", "ndim", "size", "capacity", "num_slots", "out_capacity",
    "fetch", "skip", "value", "task_index", "task_count", "node_id",
}


# ---------------------------------------------------------------------------
# per-module analysis
# ---------------------------------------------------------------------------


def _dotted(node: ast.AST) -> str:
    """'a.b.c' for Name/Attribute chains, '' otherwise."""
    parts: list = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_set_expr(node: ast.AST) -> bool:
    if isinstance(node, ast.Set):
        return True
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if name in ("set", "frozenset"):
            return True
        if name.endswith((".intersection", ".union", ".difference",
                          ".symmetric_difference")):
            # conservative: only when the receiver is itself a set expr
            return isinstance(node.func, ast.Attribute) and _is_set_expr(
                node.func.value
            )
    return False


def _is_static_arg(node: ast.AST) -> bool:
    """Arguments whose float()/int()/bool() coercion is host-side by
    construction: literals, len()/env lookups, static plan attributes."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if name.split(".")[-1] in STATIC_CALLS:
            return True
        if name.startswith(("os.environ", "os.getenv")):
            return True
    if isinstance(node, ast.Attribute) and node.attr in STATIC_ATTRS:
        return True
    if isinstance(node, ast.Subscript):
        # x.shape[0] and friends
        return _is_static_arg(node.value)
    if isinstance(node, (ast.BinOp, ast.UnaryOp)):
        kids = ([node.operand] if isinstance(node, ast.UnaryOp)
                else [node.left, node.right])
        return all(_is_static_arg(k) for k in kids)
    if isinstance(node, ast.Name) and node.id in ("capacity", "n", "cap"):
        return True
    return False


def _contains_traced_expr(node: ast.AST) -> bool:
    """Does the expression contain a jnp/lax VALUE-producing call (a
    definite tracer branch when used as a Python condition)? Bare dtype
    attributes (``jnp.float32``) and metadata calls (``jnp.issubdtype``)
    are static and excluded."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d.startswith(("jnp.", "jax.lax.", "lax.")) and (
                d.split(".")[-1] not in TRACED_STATIC_CALLS
            ):
                return True
    return False


class _FunctionInfo:
    def __init__(self, qualname: str, node: ast.AST, parent: "str | None"):
        self.qualname = qualname
        self.node = node
        self.parent = parent  # enclosing function qualname
        self.calls: set = set()  # bare names this function calls


class _ModuleAnalyzer(ast.NodeVisitor):
    """One pass to index functions, call edges, and tracing-call seeds."""

    def __init__(self) -> None:
        self.functions: dict[str, _FunctionInfo] = {}
        self.by_name: dict[str, list] = {}  # bare name -> qualnames
        self.seeds: set = set()  # qualnames passed to jit/cond/...
        self._stack: list = []

    def _qual(self, name: str) -> str:
        return ".".join([f for f in self._stack] + [name])

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    def _visit_func(self, node) -> None:
        qual = self._qual(node.name)
        parent = ".".join(self._stack) if self._stack else None
        self.functions[qual] = _FunctionInfo(qual, node, parent)
        self.by_name.setdefault(node.name, []).append(qual)
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Call(self, node: ast.Call) -> None:
        fname = _dotted(node.func).split(".")[-1]
        if fname in TRACING_CALLS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    self.seeds.add(arg.id)
        if self._stack:
            qual = ".".join(self._stack)
            info = self.functions.get(qual)
            if info is not None and isinstance(node.func, ast.Name):
                info.calls.add(node.func.id)
        self.generic_visit(node)


def _trace_path_functions(analyzer: _ModuleAnalyzer, relpath: str) -> set:
    """Fixpoint of: seed methods by name/layer, functions passed to tracing
    calls, their nested functions, and (same-module) callees."""
    parts = relpath.split("/")
    # classify by components so files outside the repo (the seeded-violation
    # tests lint temp copies) still land in the right layer
    sub = parts[parts.index(PACKAGE) + 1:] if PACKAGE in parts else parts
    in_trace_layer = (len(sub) >= 2 and sub[0] in TRACE_DIRS) or (
        "/".join(sub[-2:]) in TRACE_FILES
    )
    traced: set = set()
    for qual, info in analyzer.functions.items():
        bare = qual.split(".")[-1]
        if in_trace_layer and bare in TRACE_METHOD_NAMES:
            traced.add(qual)
        if in_trace_layer and bare in TRACE_SEED_NAMES:
            traced.add(qual)
        if bare in analyzer.seeds:
            traced.add(qual)
        for dec in getattr(info.node, "decorator_list", ()):
            d = _dotted(dec if not isinstance(dec, ast.Call) else dec.func)
            if d.split(".")[-1] in ("jit",):
                traced.add(qual)
    changed = True
    while changed:
        changed = False
        for qual, info in analyzer.functions.items():
            if qual in traced:
                continue
            # nested inside a traced function -> traced (defined+called at
            # trace time)
            if info.parent and any(
                t == info.parent or info.parent.startswith(t + ".")
                for t in traced
            ):
                traced.add(qual)
                changed = True
                continue
            # called from a traced function in this module -> traced
            bare = qual.split(".")[-1]
            for t in traced:
                tinfo = analyzer.functions.get(t)
                if tinfo is not None and bare in tinfo.calls:
                    traced.add(qual)
                    changed = True
                    break
    return traced


class _RuleVisitor(ast.NodeVisitor):
    def __init__(self, relpath: str, traced: set,
                 findings: list) -> None:
        self.relpath = relpath
        self.traced = traced
        self.findings = findings
        self._stack: list = []

    # -- helpers ------------------------------------------------------------
    def _qual(self) -> str:
        return ".".join(self._stack) if self._stack else "<module>"

    def _in_trace_path(self) -> bool:
        qual = self._qual()
        return any(
            qual == t or qual.startswith(t + ".") for t in self.traced
        )

    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        self.findings.append(Finding(
            self.relpath, getattr(node, "lineno", 0), rule, self._qual(),
            message,
        ))

    # -- structure ----------------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    def _visit_func(self, node) -> None:
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]
        for d in defaults:
            mutable = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call)
                and _dotted(d.func) in ("list", "dict", "set")
                and not d.args and not d.keywords
            )
            if mutable:
                self.findings.append(Finding(
                    self.relpath, d.lineno, "DFTPU106",
                    ".".join(self._stack + [node.name]),
                    "mutable default argument is shared across calls "
                    "(cross-query state on a long-lived worker); default "
                    "to None and allocate inside",
                ))
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- rules --------------------------------------------------------------
    @staticmethod
    def _is_tracing_api(name: str) -> bool:
        """Calls that belong to the distributed-tracing span surface
        (spans.py, runtime/tracing.py): any receiver/attribute chain naming a
        tracer (`self._tracer.span`, `tr.event`, `NULL_TRACER...`), the
        module-level span constructors, the profiler annotation a live
        span doubles as (`jax.profiler.TraceAnnotation`), and the
        monotonic clocks the span layer is built on. `jax.named_scope` is
        NOT one of them: it names the ops a traced function lowers to, at
        trace time, and is the one instrumentation that belongs there."""
        if name in ("time.monotonic", "time.perf_counter",
                    "time.perf_counter_ns", "time.monotonic_ns"):
            return True
        parts = name.split(".")
        if any("tracer" in p.lower() for p in parts):
            return True
        return parts[-1] in ("start_span", "end_span", "worker_span",
                             "finish_reserved", "open_root", "trace_call",
                             "fetch_call", "record_span", "worker_phase",
                             "TraceAnnotation",
                             "StepTraceAnnotation") or (
            len(parts) > 1 and parts[-1] in ("span", "event")
            and parts[-2] in ("tr", "tracing")
        )

    @staticmethod
    def _is_telemetry_api(name: str) -> bool:
        """Calls that belong to the telemetry / event-log surface
        (runtime/telemetry.py, runtime/eventlog.py): any receiver or
        attribute chain naming a telemetry object (`self.telemetry...`,
        `registry.counter`, `eventlog.log`), the module-level
        `log_event`, and metric-mutation methods on receivers that look
        like metrics (`*_counter.inc`, `hist.observe`)."""
        parts = name.split(".")
        if any("telemetry" in p.lower() or "eventlog" in p.lower()
               for p in parts):
            return True
        if parts[-1] in ("log_event", "render_openmetrics",
                         "merge_snapshots"):
            return True
        if len(parts) > 1 and parts[-1] in ("inc", "dec", "observe",
                                            "set_function"):
            recv = parts[-2].lower()
            return any(h in recv for h in (
                "counter", "gauge", "histogram", "metric", "_tm_",
            )) or recv.startswith("tm_") or recv.endswith("_tm")
        return False

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        if self._in_trace_path():
            if self._is_telemetry_api(name):
                self._emit(
                    node, "DFTPU110",
                    f"{name}() inside a traced function: telemetry and "
                    "event-log instrumentation must stay host-side — "
                    "under jit the call runs once at trace time (one "
                    "phantom increment/event per COMPILE, nothing per "
                    "execution) and a Tracer argument errors",
                )
            elif self._is_tracing_api(name):
                self._emit(
                    node, "DFTPU109",
                    f"{name}() inside a traced function: tracing "
                    "instrumentation must stay host-side — a span or "
                    "monotonic-clock read under jit runs once at trace "
                    "time and bakes that instant into every compiled "
                    "re-execution (and times nothing)",
                )
            elif name in ("float", "int", "bool") and node.args and not (
                _is_static_arg(node.args[0])
            ):
                self._emit(
                    node, "DFTPU101",
                    f"{name}() coercion inside a traced function: on a "
                    "Tracer this raises (or bakes a trace-time constant); "
                    "use jnp casts / keep the value traced",
                )
            elif (name.startswith("np.") or name.startswith("numpy.")) and (
                name.split(".")[-1] not in NP_STATIC_MEMBERS
            ):
                self._emit(
                    node, "DFTPU103",
                    f"{name}() inside a traced function: numpy executes "
                    "at trace time on host — a Tracer argument errors, a "
                    "static argument silently bakes a constant; use jnp "
                    "or hoist to load time",
                )
            elif name.split(".")[0] in ("time", "random"):
                self._emit(
                    node, "DFTPU105",
                    f"{name}() inside a traced function: evaluated once "
                    "at trace time, every compiled re-execution replays "
                    "that single value (nondeterministic across "
                    "processes, stale within one)",
                )
        self.generic_visit(node)

    def _check_branch(self, node, test) -> None:
        if self._in_trace_path() and _contains_traced_expr(test):
            self._emit(
                node, "DFTPU102",
                "Python control flow on a jnp/lax expression inside a "
                "traced function: raises ConcretizationTypeError under "
                "jit; use jnp.where / lax.cond",
            )

    def visit_If(self, node: ast.If) -> None:
        self._check_branch(node, node.test)
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_branch(node, node.test)
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert) -> None:
        self._check_branch(node, node.test)
        self.generic_visit(node)

    def _check_iter(self, node, it) -> None:
        if _is_set_expr(it):
            self._emit(
                node, "DFTPU104",
                "iteration over an unordered set expression: order "
                "follows the process hash seed, breaking byte-identical "
                "plans/fingerprints across processes; wrap in sorted()",
            )

    def visit_For(self, node: ast.For) -> None:
        self._check_iter(node, node.iter)
        self.generic_visit(node)

    def visit_comprehension(self, node: ast.comprehension) -> None:
        self._check_iter(node, node.iter)
        self.generic_visit(node)

    def visit_Call_iterables(self, node):  # pragma: no cover - helper
        pass


def _lint_file(path: str, findings: list) -> None:
    relpath = os.path.relpath(path, REPO_ROOT).replace(os.sep, "/")
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        findings.append(Finding(relpath, e.lineno or 0, "DFTPU100",
                                "<module>", f"syntax error: {e.msg}"))
        return
    analyzer = _ModuleAnalyzer()
    analyzer.visit(tree)
    traced = _trace_path_functions(analyzer, relpath)
    # list()/tuple()/sorted-free join over set expressions at any position
    rv = _RuleVisitor(relpath, traced, findings)
    rv.visit(tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name in ("list", "tuple") and node.args and _is_set_expr(
                node.args[0]
            ):
                findings.append(Finding(
                    relpath, node.lineno, "DFTPU104", "<module>",
                    f"{name}() over an unordered set expression: element "
                    "order follows the process hash seed; wrap in "
                    "sorted()",
                ))


def _package_files() -> list:
    out: list = []
    pkg_root = os.path.join(REPO_ROOT, PACKAGE)
    for dirpath, _dirs, files in os.walk(pkg_root):
        for f in sorted(files):
            if f.endswith(".py"):
                out.append(os.path.join(dirpath, f))
    return sorted(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="files to lint (default: the whole package)")
    ap.add_argument("--allowlist", default=DEFAULT_ALLOWLIST)
    ap.add_argument("--json", action="store_true",
                    help="emit findings as JSON")
    args = ap.parse_args(argv)

    files = args.files or _package_files()
    for f in files:
        if not os.path.exists(f):
            print(f"no such file: {f}", file=sys.stderr)
            return 2
    findings: list = []
    for f in files:
        _lint_file(os.path.abspath(f), findings)

    allow = load_allowlist(args.allowlist)
    violations, allowed, stale = apply_allowlist(
        findings, allow, check_stale=not args.files
    )

    if args.json:
        # stdout is the JSON document, nothing else — machine consumers
        # json.loads() it directly; the verdict rides the exit code
        print(json.dumps({
            "violations": [f.__dict__ for f in violations],
            "allowed": [f.__dict__ for f in allowed],
            "stale_allowlist": [list(k) for k in stale],
        }, indent=2))
        return 1 if (violations or stale) else 0
    return report_text(violations, allowed, stale, args.allowlist,
                       REPO_ROOT, "tracer-safety", len(files))


if __name__ == "__main__":
    sys.exit(main())
