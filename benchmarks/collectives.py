"""Benchmark: §3.3.2 — TAB one-shot vs NVLink-ring collectives, as HLO
op counts on a virtual 8-device CPU mesh.

Demonstrates Enabler 1 structurally: the ring allreduce lowers to 2(N-1)
collective-permute steps in the HLO while the TAB schedule is a single
all-reduce/psum op.  The mesh is 8 forced host devices of the CPU
backend, so the microseconds in the rows time XLA:CPU and are not a
performance claim; the op counts are the reproducible artifact.

``run()`` re-executes this module in a child process pinned to the CPU
(``JAX_PLATFORMS=cpu``): the parent has usually loaded JAX already, and
on a TPU host a child reaching for the chip would contend with it.  A
failed child raises.

Run standalone:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.collectives
"""
from __future__ import annotations

import functools
import os
import re
import subprocess
import sys
import time


def _inner() -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P, AxisType
    from repro.core import tab

    n = 8
    mesh = jax.make_mesh((n,), ("model",), axis_types=(AxisType.Auto,))
    rows = []
    x = jnp.asarray(np.random.RandomState(0).randn(n * 256, 256), jnp.float32)

    for sched in ("tab", "ring"):
        f = jax.jit(jax.shard_map(
            functools.partial(tab.allreduce, axis_name="model",
                              schedule=sched),
            mesh=mesh, in_specs=P("model"), out_specs=P("model"),
            check_vma=False))
        f(x).block_until_ready()
        t0 = time.perf_counter()
        iters = 20
        for _ in range(iters):
            out = f(x)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / iters * 1e6
        hlo = jax.jit(jax.shard_map(
            functools.partial(tab.allreduce, axis_name="model",
                              schedule=sched),
            mesh=mesh, in_specs=P("model"), out_specs=P("model"),
            check_vma=False)).lower(x).compile().as_text()
        # trip-count-aware: the ring's permutes live inside fori_loops
        from repro.launch.hlo_cost import module_cost
        counts = module_cost(hlo)["collective_counts"]
        n_perm = int(counts.get("collective-permute", 0))
        n_ar = int(counts.get("all-reduce", 0))
        rows.append(f"collective_allreduce_{sched},{us:.1f},"
                    f"permute_steps={n_perm} allreduce_ops={n_ar} "
                    f"(ring expects 2(N-1)={2*(n-1)} steps, tab expects 1 op)")
    return rows


def run() -> list[str]:
    if os.environ.get("REPRO_COLLECTIVES_INNER") == "1":
        return _inner()
    # re-exec on 8 virtual CPU devices
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["REPRO_COLLECTIVES_INNER"] = "1"
    env.setdefault("PYTHONPATH", "src")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.collectives"],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    rows = [l for l in out.stdout.splitlines() if l.startswith("collective")]
    if out.returncode != 0 or not rows:
        raise RuntimeError(f"collectives child failed (rc "
                           f"{out.returncode}): {out.stderr[-2000:]}")
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
