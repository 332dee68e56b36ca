"""Paper Figure 7 — multi-GPU / multi-node scaling.

Runs distributed GEEK (shard_map) across 1/2/4/8 fake host devices in
subprocesses (device count is fixed at backend init, hence the isolation).
The children are CPU-only: on an accelerator the parent refuses to fork
them (``common.require_cpu_parent``), and a failed child raises.
On real hardware the same program scales across chips; here the shape of
the curve (work split + stable radius) is what is validated — wall-clock
on one CPU core cannot speed up, so we report per-device work items too.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

_CHILD = """
import time, collections
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core.distributed import make_fit_dense
from repro.core.geek import GeekConfig
from repro.data.synthetic import sift_like

g = len(jax.devices())
data = sift_like(jax.random.PRNGKey(0), n={n}, k=64)
cfg = GeekConfig(m=40, t=64, silk_l=5, delta=10, k_max=256, pair_cap=1 << 14)
mesh = Mesh(np.array(jax.devices()), ("data",))
fit = make_fit_dense(mesh, cfg)
x = jax.device_put(data.x, NamedSharding(mesh, P("data", None)))
out = fit(x, jax.random.PRNGKey(1)); jax.block_until_ready(out)  # compile
t0 = time.time()
out = fit(x, jax.random.PRNGKey(1)); jax.block_until_ready(out)
dt = time.time() - t0
lab, c, cv, ks, rad, ovf = out
r = float(jnp.where(cv, rad, 0).sum() / jnp.maximum(cv.sum(), 1))
print("RESULT,%d,%.3f,%d,%.4f" % (g, dt, int(ks), r))
"""


def run(quick: bool = True, n: int = 8192) -> None:
    from benchmarks.common import require_cpu_parent, subprocess_env
    require_cpu_parent("bench_scaling")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for g in ([1, 4] if quick else [1, 2, 4, 8]):
        env = subprocess_env(repo, host_devices=g)
        out = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_CHILD.format(n=n))],
            env=env, capture_output=True, text=True, timeout=900)
        if out.returncode != 0:
            raise RuntimeError(f"fig7 child (devices={g}) exited "
                               f"{out.returncode}: {out.stderr[-500:]}")
        for line in out.stdout.splitlines():
            if line.startswith("RESULT"):
                _, gg, dt, ks, r = line.split(",")
                print(f"fig7/devices={gg},{float(dt)*1e6:.0f},"
                      f"k*={ks};radius={r};per_dev_points={n//int(gg)}",
                      flush=True)


if __name__ == "__main__":
    run(quick=False)
