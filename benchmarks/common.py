"""Shared benchmark utilities: timing protocol, host provenance, rows."""
from __future__ import annotations

import os
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.platform import host_device_env


class DeviceChildError(RuntimeError):
    """A harness would fork JAX children while this process is not on the
    CPU: a chip belongs to one process, so a child could not reach it."""


def require_cpu_parent(harness: str) -> None:
    """Refuse to fork device children unless this process runs on CPU.

    The forced-host-device children exist to fake a multi-device CPU; on
    an accelerator the parent already holds the chip (and the real
    devices need no faking), so forking would fail or hang the child.
    """
    backend = jax.default_backend()
    if backend != "cpu":
        raise DeviceChildError(
            f"{harness} forks one JAX child per device count, which only "
            f"works on the CPU backend; this process runs on {backend!r} "
            "and holds the device")


def subprocess_env(repo_root: str, host_devices: int | None = None) -> dict:
    """Environment for a benchmark subprocess (fresh JAX backend).

    Device count is fixed at backend init, so multi-device benches fork
    children instead of reconfiguring in-process. This routes the
    ``XLA_FLAGS`` merge through ``repro.utils.platform.host_device_env``
    (one implementation, not per-bench string building), pins
    ``PYTHONPATH`` to the repo's ``src``, and pins the child to the CPU
    backend so it never contends for an accelerator.
    """
    env = (dict(os.environ) if host_devices is None
           else host_device_env(host_devices))
    env["PYTHONPATH"] = os.path.join(repo_root, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def host_class() -> str:
    """Coarse provenance class of the machine producing a report.

    Benchmark numbers are only comparable within a class — throughput
    recorded on a GitHub-hosted runner says nothing about a developer
    workstation. ``check_regress`` refuses to gate across classes
    (soft-skip with a notice by default, hard error with
    ``--strict-host``), so a baseline regenerated on the wrong machine
    fails loudly instead of producing phantom regressions.
    """
    if os.environ.get("GITHUB_ACTIONS"):
        return "github-hosted-runner"
    return f"dev/{platform.machine()}"


def host_info() -> dict:
    """The ``host`` provenance block shared by every benchmark report."""
    return {
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "platform": platform.platform(),
        "jax": jax.__version__,
        "host_class": host_class(),
    }


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-clock seconds of a blocking call (post-warmup)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        times.append(time.time() - t0)
    return float(np.median(times))


def mean_radius(radius, valid) -> float:
    r = jnp.where(valid, radius, 0.0)
    return float(r.sum() / jnp.maximum(valid.sum(), 1))


def emit(name: str, seconds: float, derived: str = "") -> None:
    print(f"{name},{seconds*1e6:.0f},{derived}", flush=True)
