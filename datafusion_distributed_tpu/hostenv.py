"""Host-environment helpers shared by the test suite and benchmark runners.

XLA:CPU's persistent compile cache stores AOT executables whose code paths
assume the COMPILING host's CPU features, while jax's cache key does not
include them — loading an entry compiled on a different physical CPU warns
"could lead to execution errors such as SIGILL" and sporadically delivers
exactly that. Environments that land on heterogeneous machines (this VM
does) must therefore fingerprint the cache directory per CPU so a migration
misses the cache instead of executing foreign machine code.
"""

from __future__ import annotations

import hashlib
import os


def xla_flag_supported(flag: str) -> bool:
    """Whether this jaxlib's XLA knows ``flag`` (name with or without the
    leading ``--``). XLA F-aborts the WHOLE process on any unknown flag in
    XLA_FLAGS (parse_flags_from_env.cc), so a flag name must never be set
    speculatively: probe the jaxlib binary — registered flag names are
    embedded as strings — before appending anything."""
    return xla_flags_supported([flag])[flag]


def xla_flags_supported(flags) -> dict:
    """Batch form of `xla_flag_supported`: {flag: bool} in ONE scan of the
    jaxlib binaries. The negative case (old jaxlib missing every probed
    flag — exactly the environment the guard exists for) must read the
    multi-hundred-MB jaxlib tree once, not once per flag."""
    names = {f.lstrip("-").split("=")[0].encode(): f for f in flags}
    cache = xla_flags_supported.__dict__.setdefault("_cache", {})
    missing = [n for n in names if n not in cache]
    if missing:
        cache.update(_jaxlib_binaries_contain(missing))
    return {f: cache[n] for n, f in names.items()}


def _jaxlib_binaries_contain(needles) -> dict:
    import glob
    import mmap

    out = {n: False for n in needles}
    try:
        import jaxlib

        root = os.path.dirname(jaxlib.__file__)
    except Exception:
        return out
    pending = set(out)
    for path in sorted(glob.glob(os.path.join(root, "**", "*.so"),
                                 recursive=True),
                       key=os.path.getsize, reverse=True):
        try:
            with open(path, "rb") as f:
                with mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as m:
                    for n in list(pending):
                        if m.find(n) != -1:
                            out[n] = True
                            pending.discard(n)
        except (OSError, ValueError):
            continue
        if not pending:
            break
    return out


def ensure_collective_timeout_flags(warn_stuck_s: int = 120,
                                    terminate_s: int = 1200) -> None:
    """Append XLA:CPU collective-timeout flags to XLA_FLAGS unless the
    caller already set them (each flag guarded by its own name, so a
    user-supplied value for one is never clobbered by the other's
    default). Must run before the first jax backend init.

    Why: 8 virtual devices time-share this box's single core; inside a
    large mesh program one participant thread can legitimately be starved
    past XLA:CPU's default 40 s collective rendezvous termination
    timeout, which F-aborts the whole process mid-collective (observed:
    all_gather rendezvous abort in the SF0.5 sweep's mesh tier).

    Each flag is probed against the installed jaxlib first: on older
    jaxlibs (0.4.x) these flags do not exist and XLA aborts every process
    that inherits them — strictly worse than the starvation they guard."""
    flags = os.environ.get("XLA_FLAGS", "")
    wanted = {
        "--xla_cpu_collective_call_warn_stuck_timeout_seconds":
            warn_stuck_s,
        "--xla_cpu_collective_call_terminate_timeout_seconds":
            terminate_s,
    }
    supported = xla_flags_supported(
        [f for f in wanted if f not in flags]
    )
    for flag, ok in supported.items():
        if ok:
            flags = f"{flags} {flag}={wanted[flag]}"
    os.environ["XLA_FLAGS"] = flags.strip()


def compile_cache_dir() -> str:
    """The directory jax's persistent compile cache uses, placing it when
    the environment has not. With JAX_COMPILATION_CACHE_DIR set, jax reads
    the variable itself and this configures nothing. Otherwise the cache
    goes to ``<checkout>/.xla_cache``: a fixed path, because the path is
    part of the cache key and a directory that moves never hits. Call
    before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(checkout, ".xla_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cpu_fingerprint() -> str:
    """Short stable id of the host CPU's feature set (x86: the
    /proc/cpuinfo flags line; elsewhere the platform processor string)."""
    try:
        with open("/proc/cpuinfo") as f:
            flags = next(
                (line for line in f if line.startswith("flags")), ""
            )
    except OSError:
        import platform

        flags = platform.processor()
    return hashlib.sha1(flags.encode()).hexdigest()[:12]
