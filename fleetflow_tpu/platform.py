"""JAX platform bootstrap: one in-process decision, stated out loud.

`init_platform()` is the one entry point. It initialises whatever platform
JAX selects in THIS process (no probe child: a chip belongs to one process
at a time, so a child that initialises the TPU takes it from its parent),
logs the result once, and returns it as JAX reports it. Nothing here falls
back: a platform that fails to initialise raises out of `jax.devices()`.

  * Serving code (sched/tpu.py) calls `init_platform()` and runs on
    whatever it finds — TPU on a TPU host, CPU where JAX_PLATFORMS=cpu.
  * Measurement entry points (chip_smoke.py, scripts/tpu_tune.py,
    `python __graft_entry__.py`) pass `require_accelerator=True`: finding
    only the CPU raises `NoAcceleratorError` instead of producing CPU
    timings under a device metric's name.
  * CPU is an explicit request: `FLEET_FORCE_CPU=1` (honoured everywhere,
    measurement entry points included — CI smoke runs), `JAX_PLATFORMS=cpu`,
    or `force_cpu(n)` called directly (tests/conftest.py, the audit CLI).

The persistent XLA compilation cache is configured here too
(`maybe_enable_compile_cache`): `JAX_COMPILATION_CACHE_DIR` wins and is
never overwritten in code; unset, the cache lives at one fixed path inside
the checkout (`COMPILE_CACHE_DEFAULT`). The cache key covers the directory
path, so a path that moves between runs never hits.
"""

from __future__ import annotations

import os
import re
import sys

# registered at import (not lazily inside maybe_enable_compile_cache) so
# the /metrics exposition surface is identical in every process — the CI
# golden pins name/type/HELP from boot, before any solve has run
from .obs.metrics import REGISTRY as _REGISTRY

__all__ = ["NoAcceleratorError", "COMPILE_CACHE_DEFAULT", "force_cpu",
           "init_platform", "maybe_enable_compile_cache",
           "verify_compile_cache", "compile_cache_info"]

# fixed, in-checkout, git-ignored: used only when JAX_COMPILATION_CACHE_DIR
# is unset
COMPILE_CACHE_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


class NoAcceleratorError(RuntimeError):
    """A measurement entry point found no accelerator."""


def _truthy(name: str) -> bool:
    return os.environ.get(name, "").lower() not in ("", "0", "false")


def _stderr_log(msg: str) -> None:
    print(f"[fleetflow.platform] {msg}", file=sys.stderr, flush=True)


def force_cpu(n_devices: int = 1) -> None:
    """Force this process onto a virtual-CPU platform with >= n_devices
    devices.  Must run before first device use (env mutation alone is too
    late once jax is imported, but the jax_platforms config and XLA_FLAGS are
    both read at backend-init time, which has not happened yet).  An existing
    too-small device-count flag is bumped, a larger one kept."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


_info: dict | None = None


def init_platform(min_devices: int = 1, *, require_accelerator: bool = False,
                  cpu_devices: int = 1, log=None) -> dict:
    """Initialise JAX's platform in this process and report it.

    Returns `{"platform", "kind", "count"}` as JAX reports them
    (`jax.devices()[0].platform`, `.device_kind`, `len(jax.devices())`).
    Raises `NoAcceleratorError` when `require_accelerator` and the platform
    is the CPU without FLEET_FORCE_CPU=1, and `RuntimeError` when fewer than
    `min_devices` devices are visible. A CPU request (FLEET_FORCE_CPU=1 or
    JAX_PLATFORMS=cpu) gets max(min_devices, cpu_devices) virtual devices
    (`cpu_devices`: what a launcher wants on CPU while taking the chips
    present on an accelerator). The first call decides and logs the
    platform line; later calls only re-check."""
    global _info
    forced = _truthy("FLEET_FORCE_CPU")
    if _info is None:
        log = log or _stderr_log
        maybe_enable_compile_cache(log)
        if forced or os.environ.get("JAX_PLATFORMS", "") == "cpu":
            force_cpu(max(min_devices, cpu_devices))
        import jax

        devices = jax.devices()
        _info = {"platform": devices[0].platform,
                 "kind": devices[0].device_kind, "count": len(devices)}
        log(f"platform={_info['platform']} device_kind={_info['kind']!r} "
            f"devices={_info['count']}"
            + (" (FLEET_FORCE_CPU=1: explicit CPU run, timings are not "
               "device metrics)" if forced else ""))
    info = dict(_info)
    if require_accelerator and info["platform"] == "cpu" and not forced:
        raise NoAcceleratorError(
            "JAX found no accelerator (platform 'cpu', "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); this "
            "entry point measures the chip and does not fall back. Set "
            "FLEET_FORCE_CPU=1 for an explicit CPU smoke run.")
    if info["count"] < min_devices:
        raise RuntimeError(
            f"{min_devices} devices required, platform {info['platform']!r} "
            f"has {info['count']}"
            + ("; the backend initialised before the virtual device count "
               "could be raised — run in a fresh process"
               if info["platform"] == "cpu" else ""))
    return info


# ---------------------------------------------------------------------------
# Persistent XLA compilation cache
# ---------------------------------------------------------------------------

_compile_cache_dir: str | None = None
_compile_cache_tried = False

_M_CACHE_ENABLED = _REGISTRY.gauge(
    "fleet_solver_compile_cache_enabled",
    "1 when the persistent XLA compilation cache"
    " (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)"
    " is active in this process")
_M_CACHE_REJECTS = _REGISTRY.counter(
    "fleet_solver_compile_cache_rejects_total",
    "Compile-cache self-checks that failed: a known-answer probe through"
    " the persistent cache raised or returned a wrong value, so the cache"
    " was disabled for this process and solves fell back to fresh"
    " compiles (a corrupt/stale cache directory must never place a fleet)")


def maybe_enable_compile_cache(log=None) -> str | None:
    """Turn on JAX's persistent compilation cache for this process.

    A cold process start then REUSES prior XLA binaries for any shape it
    has compiled before — the other half of the warm-path story next to
    shape bucketing (solver/buckets.py): bucketing collapses shape drift
    onto few executables, the persistent cache carries those executables
    across process restarts.

    Where JAX_COMPILATION_CACHE_DIR is set JAX already has the directory:
    only the thresholds are set here and `jax_compilation_cache_dir` is
    never touched. Unset, the cache goes to COMPILE_CACHE_DEFAULT. Entries
    key on the XLA program, the device kind, the jax/jaxlib version and the
    compile flags, so CPU and TPU runs share a directory without
    cross-pollution and upgrades repopulate rather than misbehave; the
    directory is never pruned by us — prune by mtime out-of-band.
    `JAX_ENABLE_COMPILATION_CACHE=false` (JAX's own switch; tests/conftest.py
    sets it) turns the cache off.

    Idempotent; safe before or after backend init. Returns the cache
    directory when enabled, else None.
    """
    global _compile_cache_dir, _compile_cache_tried
    if _compile_cache_tried:
        return _compile_cache_dir
    _compile_cache_tried = True
    import jax

    if not jax.config.jax_enable_compilation_cache:
        _M_CACHE_ENABLED.set(0)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    try:
        if not path:
            path = COMPILE_CACHE_DEFAULT
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
    except OSError as e:            # read-only checkout: run uncached
        _M_CACHE_ENABLED.set(0)
        (log or _stderr_log)(f"compile cache disabled: {e}")
        return None
    # the fused solve pipeline is the target: cache every entry, even
    # fast-compiling ones (a 0.3 s kernel x 30 shapes is still seconds
    # of cold-start), and skip the default size floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _compile_cache_dir = path
    _M_CACHE_ENABLED.set(1)
    return path


_cache_verified = False


def verify_compile_cache(log=None) -> bool:
    """Known-answer self-check of the persistent compile cache.

    A cache directory survives jax upgrades by keying entries on version
    and flags, but it does NOT survive torn writes (a process killed mid
    -serialize), bit rot on shared scratch, or a truncating copy — and a
    corrupt entry surfaces as a deserialize error (or worse, wrong
    numerics) at first solve. Run once per process, after the backend is
    decided and the cache is enabled: compile-and-run a tiny probe with a
    known answer THROUGH the cache. A raise or a wrong value rejects the
    cache — `fleet_solver_compile_cache_rejects_total` increments, the
    cache is switched off (`jax_enable_compilation_cache`; the directory
    setting is left alone), and every subsequent solve compiles fresh
    (slow is recoverable; wrong placements are not).

    Returns True when the cache is enabled and passed (or already
    verified), False when disabled or just rejected.
    """
    global _cache_verified, _compile_cache_dir
    if _compile_cache_dir is None:
        return False
    if _cache_verified:
        return True
    import jax
    import jax.numpy as jnp

    def _probe(x):
        # distinctive constants: this probe's cache key should never
        # collide with a real solver executable
        return (x * jnp.int32(48271)
                + jnp.arange(16, dtype=jnp.int32)).sum()

    expect = 7 * 48271 * 16 + sum(range(16))
    try:
        got = int(jax.jit(_probe)(jnp.int32(7)))
        ok = got == expect
        err = None if ok else f"probe answered {got}, expected {expect}"
    except Exception as e:  # deserialize failure, backend abort, ...
        ok, err = False, repr(e)
    if ok:
        _cache_verified = True
        return True
    _M_CACHE_REJECTS.inc()
    rejected_dir = _compile_cache_dir
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()   # the enabled check is memoized
    _compile_cache_dir = None
    _M_CACHE_ENABLED.set(0)
    (log or _stderr_log)(
        f"compile cache REJECTED ({err}); dir={rejected_dir} switched off,"
        f" falling back to fresh compiles")
    return False


def compile_cache_info() -> dict:
    """{'enabled', 'dir', 'entries'} for the smoke's report and the
    metrics surfaces. `entries` counts files currently in the cache
    directory (best effort)."""
    d = _compile_cache_dir
    entries = 0
    if d:
        try:
            entries = sum(1 for n in os.listdir(d)
                          if not n.startswith("."))
        except OSError:
            entries = -1
    return {"enabled": d is not None, "dir": d, "entries": entries}
