"""Computation-platform configuration shared by CLIs, benches, the server.

Every launcher used to hand-roll its own ``XLA_FLAGS`` environment
string (``--xla_force_host_platform_device_count=4`` in docstrings,
subprocess env dicts, example preambles). This module is the one place
that knows how those flags are spelled and *when* they can still take
effect: XLA reads them at backend initialization, so they must be set
before the first JAX computation (importing ``jax`` is fine — backends
initialize lazily on first device use).

Four entry points:

- ``set_platform(platform, host_device_count=)`` — process-wide setup
  for CLI ``main()``s (call before any JAX op; raises if the backend is
  already live and the request cannot take effect).
- ``enable_compile_cache()`` — JAX's persistent compilation cache at
  ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
- ``host_device_env(n, base=)`` — a merged environment dict for
  *subprocess* launches (bench_scaling, the distributed tests), so
  child processes get the flag without string surgery at call sites.
- ``add_platform_args(parser)`` / ``apply_platform_args(args)`` — the
  shared argparse surface (``--platform`` / ``--host-devices``) used by
  ``launch/serve_cluster.py``, ``launch/cluster.py``, and the benches.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def _merge_xla_flags(flags: str, n: int) -> str:
    """Return ``flags`` with the forced-host-device count set to ``n``.

    Any existing ``--xla_force_host_platform_device_count=...`` token is
    replaced (not duplicated — XLA honors the last occurrence, but a
    doubled flag reads as a mistake in ``ps`` output); every other token
    is preserved verbatim.
    """
    kept = [tok for tok in flags.split()
            if not tok.startswith(_FORCE_FLAG + "=")]
    return " ".join(kept + [f"{_FORCE_FLAG}={int(n)}"])


def host_device_env(n: int, base: dict | None = None) -> dict:
    """Environment dict forcing ``n`` fake host devices in a subprocess.

    Parameters
    ----------
    n : int
        Host (CPU) device count for ``XLA_FLAGS``.
    base : dict or None
        Environment to extend (default: a copy of ``os.environ``).
        The input is never mutated.

    Returns
    -------
    dict
        ``base`` copied, with ``XLA_FLAGS`` merged via
        ``_merge_xla_flags`` — existing non-device-count flags survive.
    """
    env = dict(os.environ if base is None else base)
    env["XLA_FLAGS"] = _merge_xla_flags(env.get("XLA_FLAGS", ""), n)
    return env


def _backend_initialized() -> bool:
    """Best-effort check whether a JAX backend is already live.

    Reads the backend cache without populating it (calling
    ``jax.devices()`` here would itself initialize the backend and make
    every subsequent ``set_platform`` a no-op). Probing internals is
    deliberate: there is no public "is the backend up yet" API, and a
    false negative only downgrades the error below to an XLA warning.
    """
    try:
        import sys
        xb = sys.modules.get("jax._src.xla_bridge")
        return bool(xb is not None and getattr(xb, "_backends", None))
    except Exception:
        return False


def set_platform(platform: str | None = None, *,
                 host_device_count: int | None = None) -> None:
    """Select the JAX platform and/or force a host device count.

    Call from a CLI ``main()`` before the first JAX computation.
    ``jax`` may already be imported (backends initialize lazily), but
    once a backend is live the XLA flag can no longer take effect —
    then this raises instead of silently serving the wrong mesh size.

    Parameters
    ----------
    platform : {"cpu", "tpu"} or None
        Target platform, set as ``jax_platforms`` so JAX initializes that
        backend only — a missing TPU then fails at the first computation
        instead of quietly running on the CPU. None keeps the default
        resolution order.
    host_device_count : int or None
        Force this many fake host devices (the multi-device CPU story:
        ``XLA_FLAGS=--xla_force_host_platform_device_count=n``). None
        leaves the flag untouched.

    Raises
    ------
    RuntimeError
        If ``host_device_count`` is requested after the backend
        initialized with a different device count, or ``platform`` after
        the backend initialized on another platform.
    """
    if host_device_count is not None:
        n = int(host_device_count)
        if _backend_initialized():
            import jax
            if len(jax.devices()) != n:
                raise RuntimeError(
                    f"set_platform(host_device_count={n}) after the JAX "
                    f"backend initialized with {len(jax.devices())} "
                    "device(s) — XLA flags are read once at backend init. "
                    "Call set_platform() earlier (before the first JAX "
                    "computation), or export XLA_FLAGS="
                    f"{_FORCE_FLAG}={n} before launching.")
        os.environ["XLA_FLAGS"] = _merge_xla_flags(
            os.environ.get("XLA_FLAGS", ""), n)
    if platform is not None:
        if platform not in ("cpu", "tpu"):
            raise ValueError(f"unknown platform {platform!r} "
                             "(expected cpu/tpu)")
        import jax
        if _backend_initialized():
            live = jax.default_backend()
            if live != platform:
                raise RuntimeError(
                    f"set_platform({platform!r}) after the JAX backend "
                    f"initialized on {live!r} — call it before the first "
                    f"JAX computation, or export JAX_PLATFORMS={platform}")
            return
        jax.config.update("jax_platforms", platform)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``), so a later run
    from the same checkout finds what an earlier one compiled; a
    per-process or temporary directory would never be found again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def worker_devices(n: int | None = None) -> tuple:
    """The local devices a per-device worker pool should run on.

    Parameters
    ----------
    n : int or None
        Number of devices wanted (the pool's worker count). ``None``
        returns every local device.

    Returns
    -------
    tuple of jax.Device
        The first ``n`` local devices, in ``jax.local_devices()`` order
        (stable, so worker *i* always pins the same device).

    Raises
    ------
    ValueError
        If fewer than ``n`` devices exist — with the remedy spelled
        out: on CPU, force fake host devices via ``set_platform`` (or
        the CLIs' ``--host-devices``) *before* JAX initializes.
    """
    import jax
    devs = tuple(jax.local_devices())
    if n is None:
        return devs
    n = int(n)
    if n < 1:
        raise ValueError(f"need at least 1 worker device, got n={n}")
    if n > len(devs):
        raise ValueError(
            f"{n} worker devices requested but only {len(devs)} local "
            f"device(s) exist — on CPU, force fake host devices BEFORE "
            f"JAX initializes: set_platform(host_device_count={n}), the "
            f"--host-devices CLI flag, or XLA_FLAGS={_FORCE_FLAG}={n}")
    return devs[:n]


def add_platform_args(parser: argparse.ArgumentParser) -> None:
    """Install the shared ``--platform`` / ``--host-devices`` flags."""
    parser.add_argument("--platform", default=None,
                        choices=["cpu", "tpu"],
                        help="JAX platform (default: jax's own resolution)")
    parser.add_argument("--host-devices", type=int, default=None,
                        help="force this many fake host (CPU) devices — "
                             "replaces hand-set XLA_FLAGS="
                             f"{_FORCE_FLAG}=n")


def apply_platform_args(args: argparse.Namespace) -> None:
    """Apply ``add_platform_args`` flags (no-op when both are unset)."""
    if getattr(args, "platform", None) is not None or \
            getattr(args, "host_devices", None) is not None:
        set_platform(args.platform, host_device_count=args.host_devices)
