"""Scale-up TPC-H parity sweep: all 22 queries, every distributed tier,
non-trivial data (default SF 0.5 — ~3M lineitem rows).

The toy-scale matrix (tests/test_tpch_distributed.py, SF 0.002) proves
semantics; this sweep proves the machinery at a scale where capacity
sizing, overflow-retry, range sample sort, and multi-chunk streaming
actually engage — the forced-heavy-distribution intent of the reference's
`tpch_correctness_test.rs:23-80`.

Usage:
    python benchmarks/sweep_sf.py [--sf 0.5] [--tiers static,adaptive,mesh8]
                                  [--queries q1,q3,...] [--out sweep.jsonl]

Each completed (tier, query) appends one JSON line so an interrupted sweep
still reports; compose the report from the JSONL afterwards
(benchmarks/compose_sweep_md.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
# spec-load the shared host-env helper: a package import HERE would import
# jax, which reads JAX_COMPILATION_CACHE_DIR (exported below) exactly once,
# as it is imported
import importlib.util as _ilu

_spec = _ilu.spec_from_file_location(
    "_dftpu_hostenv",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "datafusion_distributed_tpu", "hostenv.py"),
)
_hostenv = _ilu.module_from_spec(_spec)
_spec.loader.exec_module(_hostenv)

# single-core box: give mesh collectives starvation headroom (see helper)
_hostenv.ensure_collective_timeout_flags()

# Persistent compile cache so a resumed/restarted sweep skips recompiling
# the same 66+ stage/mesh programs (mesh q1 reload: 21 s -> 4.4 s).
# Fingerprinted per CPU like tests/conftest.py: XLA:CPU AOT entries embed
# host machine features, and loading them on a different host risks SIGILL.
# jax reads the variable itself, so it must be exported before its first
# import.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.expanduser("~"), ".cache",
        f"dftpu_sweep_xla_{_hostenv.cpu_fingerprint()}",
    )
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)

QUERIES_DIR = "/root/reference/testdata/tpch/queries"


def _frames_match(dist, single) -> str | None:
    """Multiset equality with float tolerance; -> None or a mismatch note."""
    import numpy as np
    import pandas as pd

    if len(dist) != len(single):
        return f"row count {len(dist)} vs {len(single)}"
    if len(single) == 0:
        return None
    ds = dist.sort_values(list(dist.columns)).reset_index(drop=True)
    ss = single.sort_values(list(single.columns)).reset_index(drop=True)
    for col in single.columns:
        a, b = ds[col], ss[col]
        if pd.api.types.is_float_dtype(b) or pd.api.types.is_float_dtype(a):
            try:
                np.testing.assert_allclose(
                    a.to_numpy(dtype=float), b.to_numpy(dtype=float),
                    rtol=5e-4, atol=1e-6,
                )
            except AssertionError:
                return f"float mismatch in {col}"
        else:
            if not (
                a.reset_index(drop=True).astype(str)
                == b.reset_index(drop=True).astype(str)
            ).all():
                return f"value mismatch in {col}"
    return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=13)
    ap.add_argument("--tiers", default="static,adaptive,mesh8")
    ap.add_argument("--queries", default=",".join(f"q{i}" for i in range(1, 23)))
    ap.add_argument("--out", default="sweep.jsonl")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--tasks", type=int, default=8)
    ap.add_argument("--rlimit-gb", type=float, default=96.0,
                    help="RLIMIT_AS cap so a capacity/compile blowup "
                         "raises MemoryError instead of OOM-killing")
    args = ap.parse_args()

    if args.rlimit_gb > 0:
        import resource

        cap = int(args.rlimit_gb * (1 << 30))
        resource.setrlimit(resource.RLIMIT_AS, (cap, cap))

    # Resumability: one OOM-kill/segfault must only cost the in-flight
    # pair. Completed (tier, query) pairs are skipped on relaunch. A pair
    # with ONE dangling `started` marker gets retried (an interrupt is
    # not a poison pair); TWO dangling markers mean it crashed the
    # process twice — record it as crashed and skip, else a poison pair
    # would crash every relaunch forever.
    done_pairs: set = set()
    started_counts: dict = {}
    if os.path.exists(args.out):
        for line in open(args.out):
            if not line.strip():
                continue
            try:
                r = json.loads(line)
            except ValueError:
                continue
            if r.get("stage") == "started":
                key = (r["tier"], r["query"])
                started_counts[key] = started_counts.get(key, 0) + 1
            elif "tier" in r and r["tier"] != "single":
                done_pairs.add((r["tier"], r["query"]))
                started_counts.pop((r["tier"], r["query"]), None)
    crashed = {k for k, n in started_counts.items() if n >= 2}

    from datafusion_distributed_tpu.data.tpchgen import gen_tpch
    from datafusion_distributed_tpu.runtime.coordinator import (
        AdaptiveCoordinator,
        Coordinator,
        InMemoryCluster,
    )
    from datafusion_distributed_tpu.sql.context import SessionContext

    # the crash being recovered from may have torn the final line; a
    # leading newline isolates it so resumes and the composer stay parseable
    if os.path.exists(args.out):
        with open(args.out, "rb+") as f:
            f.seek(0, 2)
            if f.tell() > 0:
                f.seek(-1, 2)
                if f.read(1) != b"\n":
                    f.write(b"\n")

    def log(**kw):
        kw["ts"] = round(time.time(), 1)
        with open(args.out, "a") as f:
            f.write(json.dumps(kw) + "\n")

    t0 = time.perf_counter()
    tables = gen_tpch(sf=args.sf, seed=args.seed)
    log(stage="datagen", sf=args.sf, seconds=round(time.perf_counter() - t0, 1),
        rows={k: t.num_rows for k, t in tables.items()})

    ctx = SessionContext()
    ctx.config.distributed_options["bytes_per_task"] = 1  # force distribution
    for name, arrow in tables.items():
        ctx.register_arrow(name, arrow)

    tiers = args.tiers.split(",")
    queries = args.queries.split(",")
    single_cache: dict = {}

    def run_single(q, df):
        if q not in single_cache:
            t = time.perf_counter()
            single_cache[q] = df._strip_quals(df.collect_table()).to_pandas()
            log(tier="single", query=q, ok=True,
                seconds=round(time.perf_counter() - t, 2),
                rows=len(single_cache[q]))
        return single_cache[q]

    cluster = InMemoryCluster(args.workers)
    for q in queries:
        path = os.path.join(QUERIES_DIR, f"{q}.sql")
        if not os.path.exists(path):
            continue
        sql = open(path).read()
        for tier in tiers:
            if (tier, q) in done_pairs:
                continue
            if (tier, q) in crashed:
                log(tier=tier, query=q, ok=False,
                    error="crashed previous sweep process (OOM/abort); "
                          "skipped on resume")
                continue
            log(stage="started", tier=tier, query=q)
            t = time.perf_counter()
            try:
                df = ctx.sql(sql)
                single = run_single(q, df)
                extra: dict = {}
                if tier == "mesh8":
                    got = df._strip_quals(
                        df.collect_distributed_table(num_tasks=args.tasks)
                    ).to_pandas()
                elif tier == "static":
                    coord = Coordinator(resolver=cluster, channels=cluster)
                    got = df._strip_quals(df.collect_coordinated_table(
                        coordinator=coord, num_tasks=args.tasks
                    )).to_pandas()
                    extra["streams"] = [
                        {k: v for k, v in m.items()}
                        for m in coord.stream_metrics.values()
                    ]
                elif tier == "adaptive":
                    coord = AdaptiveCoordinator(
                        resolver=cluster, channels=cluster
                    )
                    got = df._strip_quals(df.collect_coordinated_table(
                        coordinator=coord, num_tasks=args.tasks
                    )).to_pandas()
                    extra["task_count_decisions"] = coord.task_count_decisions
                    extra["partial_decisions"] = {
                        str(k): v for k, v in coord.partial_decisions.items()
                    }
                else:
                    raise ValueError(tier)
                mism = _frames_match(got, single)
                retries = getattr(df, "last_retry_count", None)
                log(tier=tier, query=q, ok=mism is None, mismatch=mism,
                    seconds=round(time.perf_counter() - t, 2),
                    rows=len(got), retries=retries, **extra)
            except Exception as e:  # keep sweeping
                log(tier=tier, query=q, ok=False,
                    error=f"{type(e).__name__}: {e}"[:300],
                    seconds=round(time.perf_counter() - t, 2))
        # Aged-process guard #2: compiled executables accumulate per
        # process (jax's jit caches plus this repo's program caches) and
        # after ~2 h of SF0.5 queries the address space exhausts — observed
        # as 32-128 MiB allocation failures on late queries. Dropping every
        # compiled-program cache between queries bounds the growth;
        # recompiles for later queries reload from the persistent cache.
        import datafusion_distributed_tpu as _dftpu

        _dftpu.clear_compile_caches()
    log(stage="done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
