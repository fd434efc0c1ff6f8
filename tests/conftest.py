"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's fake-cluster strategy (SURVEY.md §4: the whole TPC
suite runs against `InMemoryChannelResolver` — a cluster faked inside one
process). Here the fake cluster is 8 virtual XLA CPU devices, which exercises
the same `jax.sharding.Mesh` + collective code paths as a real TPU pod slice.
"""

import os
import sys

# hard override: tests always run on the virtual CPU mesh, whatever
# JAX_PLATFORMS the caller exported
os.environ["JAX_PLATFORMS"] = "cpu"
# static plan verification (plan/verify.py) runs STRICT by default under
# tests: every planned/dispatched plan in the suite must verify clean, and
# a verifier false-positive is itself a test failure. The library default
# outside tests stays "warn".
os.environ.setdefault("DFTPU_VERIFY_PLANS", "strict")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Instrumented deadlock/race harness (runtime/lockcheck.py) for the
# heavily-threaded suites: when a pytest invocation TARGETS the serving /
# stage-scheduler / data-plane files, export DFTPU_LOCK_CHECK=1 before
# the package import below installs its lock factories — their seeded
# chaos/churn schedules then double as a race harness (observed
# lock-order asserted against tools/check_concurrency.py's static graph;
# a cycle raises with both acquisition stacks instead of hanging).
# setdefault: DFTPU_LOCK_CHECK=0 still opts a run out explicitly.
_LOCKCHECK_SUITES = ("test_serving", "test_stage_scheduler",
                     "test_data_plane", "test_shm_plane",
                     "test_adaptivity", "test_result_cache")
if any(s in a for a in sys.argv for s in _LOCKCHECK_SUITES):
    os.environ.setdefault("DFTPU_LOCK_CHECK", "1")
# Resource-leak harness (runtime/leakcheck.py): the suites whose seeded
# chaos/churn/hedging schedules double as a leak harness run with it
# armed when targeted directly — query-end sweeps must find zero
# surviving tracked resources (strict raises ResourceLeakError with the
# acquisition stack). setdefault: DFTPU_LEAK_CHECK=0 still opts out.
_LEAKCHECK_SUITES = ("test_serving", "test_data_plane",
                     "test_pipelined_shuffle", "test_memory_pressure",
                     "test_hedging_recovery", "test_resource_lifecycle",
                     "test_result_cache")
if any(s in a for a in sys.argv for s in _LEAKCHECK_SUITES):
    os.environ.setdefault("DFTPU_LEAK_CHECK", "strict")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# single-core box: give mesh collectives starvation headroom (shared
# helper; flags must land before the first backend init, which no package
# module triggers at import time)
from datafusion_distributed_tpu.hostenv import (  # noqa: E402
    cpu_fingerprint,
    ensure_collective_timeout_flags,
)

ensure_collective_timeout_flags()

import jax  # noqa: E402

# Force the CPU in jax's own config too: a test process never initializes
# an accelerator backend.
jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache for the suite's SINGLE-device programs (the
# bulk of its compile time: oracle runs, plan execution, worker paths).
# Multi-device (mesh-8) executables are deliberately NOT cached — serializing
# them aborts the process (see the patch below) — so the distributed
# matrices recompile each run; their per-case cost is bounded by module-
# scoped fixtures reusing one compiled program per query within a run.
# DFTPU_TEST_CACHE=0 disables.
#
# The cache DIRECTORY is fingerprinted by the host's CPU flags: this VM
# lands on heterogeneous physical CPUs across runs, and XLA's cache key
# does NOT include host machine features — it happily loads an AOT
# executable compiled on a host with e.g. +prefer-no-scatter onto one
# without it, warning "could lead to execution errors such as SIGILL".
# That is the best available explanation for the suite's sporadic
# mid-run SIGSEGVs (different test each time, every file passing in
# isolation): a migration now MISSES the cache instead of executing
# foreign machine code.


def pytest_configure(config):
    # tier-1 runs with `-m 'not slow'`; heavy multi-fault sweeps and other
    # long-tail tests opt out of it via this marker
    config.addinivalue_line(
        "markers",
        "slow: heavy tests (multi-fault chaos sweeps) excluded from the "
        "tier-1 `-m 'not slow'` run",
    )


_test_cache = os.environ.get(
    "DFTPU_TEST_CACHE",
    os.path.join(os.path.expanduser("~"), ".cache",
                 f"dftpu_test_xla_{cpu_fingerprint()}"),
)
if _test_cache != "0":
    # a cache placed from outside (JAX_COMPILATION_CACHE_DIR, which jax
    # reads itself) wins over the suite's own directory
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_test_cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _test_cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    # Serializing MULTI-device executables on the CPU backend aborts the
    # process (XLA CHECK failure inside put_executable_and_time, observed
    # jax 0.9 with the 8-device virtual mesh). Single-device programs
    # serialize fine and are most of the suite's compile time. Skip cache
    # writes for multi-device executables; they then never have cache
    # entries, so no multi-device reads happen either.
    from jax._src import compilation_cache as _cc

    _orig_put = _cc.put_executable_and_time

    def _single_device_only_put(cache_key, module_name, executable,
                                backend, compile_time):
        # DFTPU_TEST_CACHE_WRITES=0: reads still hit a pre-warmed cache but
        # nothing is serialized. Needed for SINGLE-process full-suite runs:
        # after several hundred in-process compiles even single-device
        # serialization segfaults (observed at tests/ 59%, crash inside
        # put_executable_and_time; the sharded runner never ages a process
        # far enough to hit it). With writes off the full suite passes in
        # one process — the crash is in the cache-write serializer, not
        # compilation or execution.
        if os.environ.get("DFTPU_TEST_CACHE_WRITES", "1") == "0":
            return None
        try:
            multi = len(executable.local_devices()) > 1
        except Exception:
            import warnings

            warnings.warn(
                "LoadedExecutable.local_devices() unavailable; persistent "
                "compile cache writes disabled entirely (suite reverts to "
                "cold compiles)", RuntimeWarning, stacklevel=2,
            )
            multi = True  # unknown shape of API: stay safe, skip write
        if multi:
            return None
        return _orig_put(cache_key, module_name, executable, backend,
                         compile_time)

    _cc.put_executable_and_time = _single_device_only_put
