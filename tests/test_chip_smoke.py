"""chip_smoke.py, rehearsed without the chip.

The script itself refuses to start without a TPU (case i). Its phases are
plain functions that never look at the device, so they run here on the
virtual CPU mesh at a tiny scale factor (case ii) — the control flow, the
entry points and the oracle comparison a chip run makes, with none of its
seconds.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import chip_smoke  # the repo root is on sys.path (tests/conftest.py)

ROOT = os.path.dirname(os.path.abspath(chip_smoke.__file__))

SF = 0.01
QUERIES = ("q1", "q3")


def test_refuses_to_start_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT,
    )
    seconds = time.perf_counter() - t0
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # refused before set-up: no phase line, and far less than the ~31 s
    # gen_tpch(1.0) takes
    assert len(lines) == 1, proc.stdout
    assert seconds < 25, seconds


@pytest.mark.parametrize("mode,changed", [("tpu", set()),
                                          ("x64", {"jax_enable_x64"})],
                         ids=["tpu", "x64"])
def test_package_import_leaves_jax_config_alone(mode, changed):
    """Nothing on the smoke's path may choose the platform, place a
    compile cache or patch jax behind the caller's back: importing the
    package changes no jax option but x64's."""
    code = (
        "import json, jax\n"
        "before = dict(jax.config.values)\n"
        "import datafusion_distributed_tpu\n"
        "after = dict(jax.config.values)\n"
        "print(json.dumps(sorted(k for k in after"
        " if after[k] != before.get(k))))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", DFTPU_PRECISION=mode)
    for name in ("DFTPU_LOCK_CHECK", "DFTPU_LEAK_CHECK"):
        env.pop(name, None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    assert set(json.loads(proc.stdout.strip().splitlines()[-1])) == changed


@pytest.fixture(scope="module")
def smoke_env():
    ctx, frames = chip_smoke.setup(SF, seed=0)
    return ctx, chip_smoke.load_queries(QUERIES, frames)


def test_load_queries_applies_the_limit(smoke_env):
    _, queries = smoke_env
    by_name = {q.name: q for q in queries}
    assert len(by_name["q3"].expected) == 10  # q3.sql ends in LIMIT 10
    assert 1 <= len(by_name["q1"].expected) <= 6


@pytest.mark.parametrize("phase", ["direct", "served", "mesh"])
def test_phase_agrees_with_oracle(smoke_env, capsys, phase):
    ctx, queries = smoke_env
    # each phase raises on any mismatch with the oracle
    getattr(chip_smoke, f"phase_{phase}")(ctx, queries)
    records = [json.loads(line)
               for line in capsys.readouterr().out.splitlines()]
    per_query = [r for r in records if "query" in r]
    assert [r["query"] for r in per_query] == list(QUERIES)
    assert all(r["phase"] == phase and r["rows"] > 0 for r in per_query)
    if phase == "mesh":
        # 4 of the 8 virtual devices, as on a four-chip host
        assert len(records[0]["devices"]) == 4


def test_phase_fails_on_a_wrong_answer(smoke_env):
    ctx, queries = smoke_env
    q1 = queries[0]
    wrong = q1.expected.copy()
    wrong["sum_qty"] = wrong["sum_qty"] * 1.01
    with pytest.raises(AssertionError):
        chip_smoke.phase_direct(ctx, [q1._replace(expected=wrong)])
