"""Static plan verifier (plan/verify.py) + tracer-safety lint gate.

Two contracts pinned here:

1. Every seeded malformed-plan class is rejected with its OWN diagnostic
   code (hand-built trees below), and the strict/warn/off mode plumbing
   behaves: strict raises before any trace/compile/dispatch, warn
   degrades to a Python warning, off bypasses.
2. The clean sweep: every plan the engine itself produces — an inlined
   battery of diverse query shapes plus (when the reference testdata is
   present) all TPC-H/TPC-DS/ClickBench snapshot-suite queries — verifies
   with ZERO errors. The whole tier-1 suite reinforces this: conftest.py
   exports DFTPU_VERIFY_PLANS=strict, so any verifier false positive
   fails the test that planned the query.

The lint gate (tools/check_tracer_safety.py) is tested by subprocess: the
shipped tree must pass clean, a seeded violation file must fail with the
expected rule codes, and the allowlist must both suppress and report
staleness.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pyarrow as pa
import pytest

from datafusion_distributed_tpu.io.parquet import arrow_to_table
from datafusion_distributed_tpu.ops.aggregate import AggSpec
from datafusion_distributed_tpu.ops.sort import SortKey
from datafusion_distributed_tpu.plan import expressions as pe
from datafusion_distributed_tpu.plan.exchanges import (
    BroadcastExchangeExec,
    CoalesceExchangeExec,
    IsolatedArmExec,
    ShuffleExchangeExec,
)
from datafusion_distributed_tpu.plan.joins import HashJoinExec, UnionExec
from datafusion_distributed_tpu.plan.physical import (
    FilterExec,
    HashAggregateExec,
    MemoryScanExec,
    SortExec,
)
from datafusion_distributed_tpu.plan.verify import (
    MODES,
    PlanVerificationError,
    enforce_verification,
    render_verified_tree,
    resolve_verify_mode,
    verify_physical_plan,
)
from datafusion_distributed_tpu.runtime.errors import is_capacity_overflow
from datafusion_distributed_tpu.schema import DataType
from datafusion_distributed_tpu.sql.context import SessionContext, VerifyReport

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO_ROOT, "tools", "check_tracer_safety.py")
REFDATA = "/root/reference/testdata"


def _table(n=64, with_string=False):
    rng = np.random.default_rng(7)
    cols = {
        "a": rng.integers(0, 10, n).astype(np.int64),
        "b": rng.normal(size=n),
    }
    if with_string:
        cols["s"] = np.asarray(
            [f"v{int(i) % 5}" for i in rng.integers(0, 100, n)], dtype=object
        )
    return arrow_to_table(pa.table(cols))


def _scan(t=None, **kw):
    t = t if t is not None else _table(**kw)
    return MemoryScanExec([t], t.schema())


# ---------------------------------------------------------------------------
# the six seeded malformed-plan classes, each with its own code
# ---------------------------------------------------------------------------


def test_schema_mismatch_unknown_column_DFTPU011():
    bad = SortExec([SortKey("no_such_col", True, False)], _scan())
    r = verify_physical_plan(bad)
    assert not r.ok
    assert "DFTPU011" in r.codes()


def test_capacity_below_ndv_estimate_DFTPU021():
    agg = HashAggregateExec(
        "single", ["a"], [AggSpec("count_star", None, "c")], _scan(),
        num_slots=4,
    )
    agg.est_rows = 1000.0  # planner NDV stamp far above the table size
    r = verify_physical_plan(agg)
    assert "DFTPU021" in r.codes()
    # degraded-but-correct: a warning (the runtime overflow check + retry
    # still guarantees results), so strict mode must NOT raise on it
    assert r.ok
    enforce_verification(agg, mode="strict")


def test_inconsistent_boundary_partition_counts_DFTPU031():
    sh = ShuffleExchangeExec(_scan(), ["a"], 4, 64)
    sh.stage_id = 0
    co = CoalesceExchangeExec(sh, 8)  # claims 8 producers; shuffle made 4
    co.stage_id = 1
    r = verify_physical_plan(co)
    assert not r.ok
    assert "DFTPU031" in r.codes()


def test_non_divisible_mesh_axis_DFTPU035():
    sh = ShuffleExchangeExec(_scan(), ["a"], 3, 64)
    sh.stage_id = 0
    co = CoalesceExchangeExec(sh, 3)
    co.stage_id = 1
    clean = verify_physical_plan(co)
    assert clean.ok  # fine on the host tier
    r = verify_physical_plan(co, mesh_axis_size=8)
    assert not r.ok
    assert "DFTPU035" in r.codes()


def test_cyclic_plan_graph_DFTPU033():
    f = FilterExec(
        pe.BinaryOp(">", pe.Col("a"), pe.Literal(3, DataType.INT64)), _scan()
    )
    f.child = f  # back-edge
    r = verify_physical_plan(f)
    assert not r.ok
    assert r.codes() == {"DFTPU033"}  # later passes must not run (or hang)


def test_custom_node_without_structural_tokens_DFTPU041():
    class OpaqueExec(MemoryScanExec):
        pass

    t = _table()
    r = verify_physical_plan(OpaqueExec([t], t.schema()))
    assert "DFTPU041" in r.codes()
    assert r.ok  # warning: it runs, it just never shares compiles

    class TokenedExec(MemoryScanExec):
        def structural_tokens(self):
            return ("tokened", 1)

    r2 = verify_physical_plan(TokenedExec([t], t.schema()))
    assert "DFTPU041" not in r2.codes()


# ---------------------------------------------------------------------------
# the remaining pass coverage
# ---------------------------------------------------------------------------


def test_filter_not_boolean_DFTPU015():
    r = verify_physical_plan(FilterExec(pe.Col("a"), _scan()))
    assert "DFTPU015" in r.codes() and not r.ok


def test_join_key_class_mismatch_DFTPU012():
    t_int, t_str = _table(), _table(with_string=True)
    j = HashJoinExec(_scan(t_int), _scan(t_str), ["a"], ["s"], "inner")
    r = verify_physical_plan(j)
    assert "DFTPU012" in r.codes() and not r.ok


def test_union_schema_mismatch_DFTPU013():
    r = verify_physical_plan(
        UnionExec([_scan(_table()), _scan(_table(with_string=True))])
    )
    assert "DFTPU013" in r.codes() and not r.ok


def test_int32_capacity_overflow_DFTPU022():
    sh = ShuffleExchangeExec(_scan(), ["a"], 1 << 16, 1 << 16)
    sh.stage_id = 0
    r = verify_physical_plan(sh)
    assert "DFTPU022" in r.codes() and not r.ok


def test_join_slots_below_build_bound_DFTPU023():
    j = HashJoinExec(_scan(), _scan(), ["a"], ["a"], "inner", num_slots=8)
    j.build.est_rows = 4096.0
    r = verify_physical_plan(j)
    assert "DFTPU023" in r.codes()
    assert r.ok  # warning only


def test_co_shuffled_join_disagreement_DFTPU034():
    p = ShuffleExchangeExec(_scan(), ["a"], 4, 64)
    p.stage_id = 0
    b = ShuffleExchangeExec(_scan(), ["a"], 8, 64)
    b.stage_id = 1
    j = HashJoinExec(p, b, ["a"], ["a"], "inner")
    r = verify_physical_plan(CoalesceExchangeExec(j, 4))
    assert "DFTPU034" in r.codes() and not r.ok


def test_unstamped_and_duplicate_stage_ids_DFTPU032():
    sh = ShuffleExchangeExec(_scan(), ["a"], 4, 64)  # stage_id = None
    r = verify_physical_plan(sh)
    assert "DFTPU032" in r.codes() and not r.ok
    a = ShuffleExchangeExec(_scan(), ["a"], 4, 64)
    a.stage_id = 0
    b = CoalesceExchangeExec(a, 4)
    b.stage_id = 0  # duplicate
    r2 = verify_physical_plan(b)
    assert "DFTPU032" in r2.codes() and not r2.ok


def test_task_lattice_unsatisfiable_DFTPU036():
    t = _table()
    sliced = MemoryScanExec([t, t, t, t], t.schema())  # 4 slices
    co = CoalesceExchangeExec(sliced, 2)  # stage runs 2 tasks
    co.stage_id = 0
    r = verify_physical_plan(co)
    assert "DFTPU036" in r.codes() and not r.ok
    arm = IsolatedArmExec(_scan(t), assigned_task=7)
    co2 = CoalesceExchangeExec(arm, 4)
    co2.stage_id = 0
    r2 = verify_physical_plan(co2)
    assert "DFTPU036" in r2.codes() and not r2.ok


def test_unhoistable_literal_warning_DFTPU042():
    f = FilterExec(
        pe.Like(pe.Col("s"), "%abc%", False), _scan(with_string=True)
    )
    r = verify_physical_plan(f)
    assert "DFTPU042" in r.codes() and r.ok
    # hoistable numeric comparisons must NOT warn
    f2 = FilterExec(
        pe.BinaryOp("<", pe.Col("a"), pe.Literal(5, DataType.INT64)), _scan()
    )
    assert "DFTPU042" not in verify_physical_plan(f2).codes()


# ---------------------------------------------------------------------------
# mode plumbing
# ---------------------------------------------------------------------------


def test_mode_resolution_precedence(monkeypatch):
    monkeypatch.delenv("DFTPU_VERIFY_PLANS", raising=False)
    assert resolve_verify_mode(None) == "warn"
    monkeypatch.setenv("DFTPU_VERIFY_PLANS", "off")
    assert resolve_verify_mode(None) == "off"
    assert resolve_verify_mode({"verify_plans": "strict"}) == "strict"
    with pytest.raises(ValueError):
        resolve_verify_mode({"verify_plans": "bogus"})
    assert set(MODES) == {"strict", "warn", "off"}


def test_enforce_modes():
    bad = SortExec([SortKey("zzz", True, False)], _scan())
    with pytest.raises(PlanVerificationError) as ei:
        enforce_verification(bad, mode="strict")
    assert "DFTPU011" in str(ei.value)
    assert not is_capacity_overflow(ei.value)  # nothing retries it
    with pytest.warns(RuntimeWarning, match="DFTPU011"):
        enforce_verification(bad, mode="warn")
    assert enforce_verification(bad, mode="off") is None


def test_strict_error_beside_a_capacity_warning_is_not_retried(monkeypatch):
    """A report that holds an error beside DFTPU021 (whose text says the
    table "will overflow") is raised on the first attempt: the re-plan
    loop acts on the error's type, and this is no capacity overflow."""
    from datafusion_distributed_tpu.sql.planner import PhysicalPlanner

    agg = HashAggregateExec(
        "single", ["a"], [AggSpec("count_star", None, "c")], _scan(),
        num_slots=4,
    )
    agg.est_rows = 1000.0
    bad = SortExec([SortKey("zzz", True, False)], agg)
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({"a": np.arange(8)}))
    ctx.sql("SET distributed.verify_plans = strict")
    df = ctx.sql("select a from t")
    planned = []
    monkeypatch.setattr(
        PhysicalPlanner, "plan",
        lambda self, logical: (planned.append(1), bad)[1],
    )
    with pytest.raises(PlanVerificationError) as ei:
        df.collect_table()
    assert {"DFTPU011", "DFTPU021"} <= set(ei.value.result.codes())
    assert "overflow" in str(ei.value)
    assert planned == [1]


def test_coordinator_rejects_malformed_plan_before_dispatch():
    from datafusion_distributed_tpu.runtime.coordinator import (
        Coordinator,
        InMemoryCluster,
    )

    sh = ShuffleExchangeExec(
        SortExec([SortKey("zzz", True, False)], _scan()), ["a"], 4, 64
    )
    sh.stage_id = 0
    bad = CoalesceExchangeExec(sh, 4)
    bad.stage_id = 1
    cluster = InMemoryCluster(2)
    coord = Coordinator(resolver=cluster, channels=cluster,
                        config_options={"verify_plans": "strict"})
    with pytest.raises(PlanVerificationError):
        coord.execute(bad)
    for w in cluster.workers.values():  # nothing was dispatched or staged
        assert not w.table_store.tables and len(w.registry) == 0


def test_session_set_verify_plans_validates():
    ctx = SessionContext()
    ctx.sql("SET distributed.verify_plans = warn")
    assert ctx.config.distributed_options["verify_plans"] == "warn"
    with pytest.raises(ValueError):
        ctx.sql("SET distributed.verify_plans = sloppy")


# ---------------------------------------------------------------------------
# worker post-decode integrity (DFTPU043) + codec round-trip (DFTPU044)
# ---------------------------------------------------------------------------


def _staged_plan():
    rng = np.random.default_rng(5)
    t = arrow_to_table(pa.table({
        "k": rng.integers(0, 8, 512), "v": rng.normal(size=512),
    }))
    from datafusion_distributed_tpu.planner.distributed import (
        DistributedConfig,
        distribute_plan,
    )

    scan = MemoryScanExec([t], t.schema())
    agg = HashAggregateExec(
        "single", ["k"], [AggSpec("sum", "v", "s")], scan, 32
    )
    return distribute_plan(agg, DistributedConfig(num_tasks=2))


def test_worker_post_decode_fingerprint_check_DFTPU043():
    from datafusion_distributed_tpu.runtime.codec import encode_plan
    from datafusion_distributed_tpu.runtime.errors import PlanIntegrityError
    from datafusion_distributed_tpu.runtime.worker import TaskKey, Worker

    staged = _staged_plan()
    stage_plan = staged.children()[0]  # the producer stage subtree
    w = Worker("mem://w0")
    obj = encode_plan(stage_plan, w.table_store)
    assert "_fp" in obj
    # pristine object registers fine
    w.set_plan(TaskKey("q", 0, 0), obj, task_count=2)
    # corrupted structural field -> classified fatal, BEFORE registration
    import copy

    bad = copy.deepcopy(obj)

    def bump_slots(o):
        if isinstance(o, dict):
            if isinstance(o.get("slots"), int):
                o["slots"] += 1
                return True
            return any(bump_slots(v) for v in o.values())
        if isinstance(o, list):
            return any(bump_slots(v) for v in o)
        return False

    assert bump_slots(bad)
    with pytest.raises(PlanIntegrityError, match="DFTPU043"):
        w.set_plan(TaskKey("q2", 0, 0), bad, task_count=2)
    assert w.registry.get(TaskKey("q2", 0, 0)) is None


def test_codec_roundtrip_assertion_DFTPU044(monkeypatch):
    """DFTPU_VERIFY_CODEC=1: a lossy user codec is caught at ENCODE time —
    fingerprint(decode(encode(plan))) != fingerprint(plan)."""
    from datafusion_distributed_tpu.runtime import codec as codec_mod
    from datafusion_distributed_tpu.runtime.codec import (
        TableStore,
        encode_plan,
        register_codec,
    )
    from datafusion_distributed_tpu.runtime.errors import PlanIntegrityError

    from datafusion_distributed_tpu.plan.physical import ExecutionPlan

    class LossyExec(ExecutionPlan):
        """Pass-through wrapper whose codec DROPS its structural tag."""

        codec_kind = "lossy_node"

        def __init__(self, child, tag=0):
            super().__init__()
            self.child = child
            self.tag = tag

        def children(self):
            return [self.child]

        def with_new_children(self, children):
            return LossyExec(children[0], self.tag)

        def schema(self):
            return self.child.schema()

        def output_capacity(self):
            return self.child.output_capacity()

        def structural_tokens(self):
            return ("lossy_node", self.tag)

    monkeypatch.setenv("DFTPU_VERIFY_CODEC", "1")
    register_codec(
        "lossy_node",
        lambda p, store: {"c": codec_mod._encode_plan_node(p.child, store)},
        lambda o, store: LossyExec(codec_mod.decode_plan(o["c"], store),
                                   tag=0),
    )
    try:
        # tag=0 round-trips exactly -> clean
        encode_plan(LossyExec(_scan(), tag=0), TableStore())
        with pytest.raises(PlanIntegrityError, match="DFTPU044"):
            encode_plan(LossyExec(_scan(), tag=7), TableStore())
    finally:
        codec_mod._USER_CODECS.pop("lossy_node", None)


# ---------------------------------------------------------------------------
# EXPLAIN VERIFY + explain_analyze integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sql_ctx():
    rng = np.random.default_rng(11)
    ctx = SessionContext()
    ctx.register_arrow("t", pa.table({
        "k": rng.integers(0, 6, 2000),
        "v": rng.normal(size=2000),
        "s": np.asarray([f"cat{i % 4}" for i in range(2000)], dtype=object),
    }))
    return ctx


def test_explain_verify_statement(sql_ctx):
    rep = sql_ctx.sql(
        "EXPLAIN VERIFY select k, count(*) c from t "
        "where s like '%at1%' group by k"
    )
    assert isinstance(rep, VerifyReport)
    assert "verification:" in rep
    # the unhoistable LIKE warning lands on the Filter node line
    assert "DFTPU042" in rep
    assert any(d.code == "DFTPU042" for d in rep.diagnostics)
    assert all(d.severity != "error" for d in rep.diagnostics)


def test_explain_verify_method_clean(sql_ctx):
    rep = sql_ctx.sql(
        "select k, sum(v) s from t group by k order by k"
    ).explain_verify(num_tasks=4)
    assert not rep.result.errors()
    assert "verification:" in rep


def test_explain_analyze_shows_verifier_warnings(sql_ctx):
    from datafusion_distributed_tpu.plan.physical import execute_plan
    from datafusion_distributed_tpu.runtime.metrics import (
        MetricsStore,
        explain_analyze,
    )

    df = sql_ctx.sql("select k from t where s like '%at2%'")
    plan = df.physical_plan()
    store = MetricsStore()
    execute_plan(plan, metrics_store=store, task_label="task0")
    text = explain_analyze(plan, store)
    assert "output_rows=" in text
    assert "DFTPU042" in text  # static finding next to runtime metrics


# ---------------------------------------------------------------------------
# clean sweep: engine-produced plans verify with zero errors
# ---------------------------------------------------------------------------

#: diverse inlined battery (every operator family; independent of the
#: reference testdata, which is absent on some images)
SWEEP_QUERIES = {
    "global_agg": "select count(*) c, sum(v) s, avg(v) a from t",
    "group_sort": "select k, sum(v) s from t group by k order by s desc",
    "filter_like": "select k from t where s like '%at3%' and v > 0.5",
    "topk": "select k, v from t order by v desc limit 7",
    "window": "select k, v, row_number() over "
              "(partition by k order by v) rn from t",
    "join": "select a.k, sum(a.v + b.v) s from t a, t b "
            "where a.k = b.k group by a.k",
    "union": "select k from t where v > 1 union all "
             "select k from t where v < -1",
    "in_list": "select count(*) c from t where k in (1, 3, 5)",
    "subquery": "select k from t where v > (select avg(v) from t)",
    "distinct": "select k, count(distinct s) u from t group by k",
}


@pytest.mark.parametrize("name", sorted(SWEEP_QUERIES))
def test_clean_sweep_inlined(sql_ctx, name):
    df = sql_ctx.sql(SWEEP_QUERIES[name])
    for plan in (df.physical_plan(), df.distributed_plan(num_tasks=4)):
        r = verify_physical_plan(plan)
        assert r.ok, f"{name}: false positives:\n{r.render()}"
    # lattice-active configs reshape stage widths; they must stay coherent
    from datafusion_distributed_tpu.planner.distributed import (
        DistributedConfig,
    )

    for cfg in (
        DistributedConfig(num_tasks=8, max_tasks_per_stage=3),
        DistributedConfig(num_tasks=8, size_tasks_to_data=True),
        DistributedConfig(num_tasks=8, cardinality_task_count_factor=2.0),
    ):
        r = verify_physical_plan(df.distributed_plan(config=cfg))
        assert r.ok, f"{name}/{cfg}: false positives:\n{r.render()}"


def _suite_queries(suite: str, names) -> list:
    qdir = os.path.join(REFDATA, suite, "queries")
    return [
        (suite, q) for q in names
        if os.path.exists(os.path.join(qdir, f"{q}.sql"))
    ]


_SNAPSHOT_CASES = (
    _suite_queries("tpch", [f"q{i}" for i in range(1, 23)])
    + _suite_queries("tpcds", [f"q{i}" for i in range(1, 100)])
    + _suite_queries("clickbench", [f"q{i}" for i in range(43)])
)


@pytest.mark.skipif(not _SNAPSHOT_CASES,
                    reason="reference testdata not present on this image")
@pytest.mark.parametrize("suite,q", _SNAPSHOT_CASES)
def test_clean_sweep_snapshot_suites(suite, q, request):
    ctx = request.getfixturevalue(f"{suite}_suite_ctx")
    sql = open(os.path.join(REFDATA, suite, "queries", f"{q}.sql")).read()
    df = ctx.sql(sql)
    r = verify_physical_plan(df.distributed_plan(num_tasks=4))
    assert r.ok, f"{suite}/{q}: false positives:\n{r.render()}"


@pytest.fixture(scope="module")
def tpch_suite_ctx():
    from datafusion_distributed_tpu.data.tpchgen import register_tpch

    ctx = SessionContext()
    register_tpch(ctx, sf=0.001, seed=0)
    return ctx


@pytest.fixture(scope="module")
def tpcds_suite_ctx():
    from datafusion_distributed_tpu.data.tpcdsgen import register_tpcds

    ctx = SessionContext()
    register_tpcds(ctx, sf=0.001, seed=0)
    return ctx


@pytest.fixture(scope="module")
def clickbench_suite_ctx():
    from datafusion_distributed_tpu.data.clickbenchgen import gen_clickbench

    ctx = SessionContext()
    ctx.register_arrow("hits", gen_clickbench(rows=2000, seed=3))
    return ctx


# ---------------------------------------------------------------------------
# tracer-safety lint gate
# ---------------------------------------------------------------------------


def _run_lint(*args):
    return subprocess.run(
        [sys.executable, LINT, *args],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )


def test_lint_shipped_tree_is_clean():
    res = _run_lint()
    assert res.returncode == 0, res.stdout + res.stderr
    assert "lint clean" in res.stdout


SEEDED_VIOLATIONS = textwrap.dedent(
    '''
    import time
    import numpy as np
    import jax.numpy as jnp

    class BadExec:
        def _execute(self, ctx):
            t = ctx.load()
            n = int(t.num_rows)            # DFTPU101
            if jnp.any(t.mask):            # DFTPU102
                x = np.cumsum(t.data)      # DFTPU103
            stamp = time.time()            # DFTPU105
            return n, stamp, x

    def encode(plan, seen={}):             # DFTPU106
        for k in set(plan.keys()):         # DFTPU104
            seen[k] = plan[k]
        return seen
    '''
)


def test_lint_gate_fails_on_seeded_violations(tmp_path):
    bad_dir = tmp_path / "datafusion_distributed_tpu" / "plan"
    bad_dir.mkdir(parents=True)
    bad = bad_dir / "seeded.py"
    bad.write_text(SEEDED_VIOLATIONS)
    res = _run_lint(str(bad), "--allowlist", os.devnull)
    assert res.returncode == 1
    for code in ("DFTPU101", "DFTPU102", "DFTPU103", "DFTPU104",
                 "DFTPU105", "DFTPU106"):
        assert code in res.stdout, f"{code} missing:\n{res.stdout}"
    assert "LINT FAILED" in res.stdout


def test_lint_allowlist_suppresses_and_requires_justification(tmp_path):
    bad_dir = tmp_path / "datafusion_distributed_tpu" / "plan"
    bad_dir.mkdir(parents=True)
    bad = bad_dir / "seeded.py"
    bad.write_text(SEEDED_VIOLATIONS)
    rel = os.path.relpath(str(bad), REPO_ROOT).replace(os.sep, "/")
    allow = tmp_path / "allow.txt"
    allow.write_text("\n".join(
        f"{rel}::{rule}::{qual}  # intentional for the test"
        for rule, qual in [
            ("DFTPU101", "BadExec._execute"),
            ("DFTPU102", "BadExec._execute"),
            ("DFTPU103", "BadExec._execute"),
            ("DFTPU105", "BadExec._execute"),
            ("DFTPU104", "encode"),
            ("DFTPU106", "encode"),
        ]
    ) + "\n")
    res = _run_lint(str(bad), "--allowlist", str(allow))
    assert res.returncode == 0, res.stdout
    assert "6 allowlisted" in res.stdout
    # an entry without a justification comment is itself an error
    allow.write_text(f"{rel}::DFTPU101::BadExec._execute\n")
    res2 = _run_lint(str(bad), "--allowlist", str(allow))
    assert res2.returncode == 2


def test_lint_json_output(tmp_path):
    import json

    bad_dir = tmp_path / "datafusion_distributed_tpu" / "plan"
    bad_dir.mkdir(parents=True)
    bad = bad_dir / "seeded.py"
    bad.write_text(SEEDED_VIOLATIONS)
    res = _run_lint(str(bad), "--allowlist", os.devnull, "--json")
    payload = json.loads(res.stdout)
    rules = {v["rule"] for v in payload["violations"]}
    assert {"DFTPU101", "DFTPU104", "DFTPU106"} <= rules


# ---------------------------------------------------------------------------
# renderer
# ---------------------------------------------------------------------------


def test_render_verified_tree_places_diagnostics_on_nodes():
    bad = SortExec([SortKey("zzz", True, False)], _scan())
    r = verify_physical_plan(bad)
    text = render_verified_tree(bad, r)
    lines = text.splitlines()
    assert lines[0].startswith("Sort")
    assert "!DFTPU011" in lines[0]
    assert "MemoryScan" in lines[1]
    assert "verification: 1 error(s)" in lines[-1]
