"""TAB vs ring collectives on a real multi-device mesh (subprocess with
forced host devices, since the main test process must stay single-device)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import tab

n = 8
from jax.sharding import AxisType
mesh = jax.make_mesh((n,), ("model",), axis_types=(AxisType.Auto,))
shard_map, _sm_kw = jax.shard_map, {"check_vma": False}

rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(n * 4, 16), jnp.float32)

def smap(fn, in_specs, out_specs):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, **_sm_kw))

# allreduce: tab == ring == jnp sum
want = np.tile(np.asarray(x).reshape(n, 4, 16).sum(0), (n, 1, 1)).reshape(n*4, 16)
for sched in ("tab", "ring"):
    f = smap(functools.partial(tab.allreduce, axis_name="model",
                               schedule=sched), P("model"), P("model"))
    got = np.asarray(f(x))
    assert np.allclose(got, want, atol=1e-4), f"allreduce {sched}"

# reduce_scatter: each device's shard of the sum
rs_t = smap(lambda v: tab.reduce_scatter(v[0], "model", schedule="tab")[None],
            P("model"), P("model"))
rs_r = smap(lambda v: tab.ring_reduce_scatter(v[0], "model")[None],
            P("model"), P("model"))
y = jnp.asarray(rng.randn(n, n * 2), jnp.float32)  # per-dev (1, 16)
a, b = np.asarray(rs_t(y)), np.asarray(rs_r(y))
assert np.allclose(a, b, atol=1e-4), "reduce_scatter mismatch"

# allgather
ag_t = smap(functools.partial(tab.allgather, axis_name="model",
                              schedule="tab"), P("model"), P(None))
ag_r = smap(functools.partial(tab.allgather, axis_name="model",
                              schedule="ring"), P("model"), P(None))
assert np.allclose(np.asarray(ag_t(x)), np.asarray(x), atol=1e-6)
assert np.allclose(np.asarray(ag_r(x)), np.asarray(x), atol=1e-6)

# all_to_all is its own inverse for a symmetric layout
a2a = smap(functools.partial(tab.tab_all_to_all, axis_name="model"),
           P("model"), P("model"))
z = jnp.arange(float(n * n)).reshape(n * n, 1)
once = a2a(z)
twice = a2a(once)
assert np.allclose(np.asarray(twice), np.asarray(z)), "a2a involution"

# p2p shift moves each shard to the next device
p2p = smap(functools.partial(tab.tab_p2p, axis_name="model"),
           P("model"), P("model"))
shifted = np.asarray(p2p(jnp.arange(float(n))[:, None])).ravel()
assert list(shifted) == [float((i - 1) % n) for i in range(n)], shifted

# Enabler 1 on real HLO: ring allreduce lowers to 2(N-1) permute steps
import re
f_ring = smap(functools.partial(tab.allreduce, axis_name="model",
                                schedule="ring"), P("model"), P("model"))
hlo = f_ring.lower(x).compile().as_text()
n_perm = len(re.findall(r"collective-permute(?:-start)?\(", hlo))
assert n_perm >= 2, f"ring should show permute steps, got {n_perm}"
f_tab = smap(functools.partial(tab.allreduce, axis_name="model",
                               schedule="tab"), P("model"), P("model"))
hlo_t = f_tab.lower(x).compile().as_text()
n_ar = len(re.findall(r"= [^=]*all-reduce(?:-start)?\(", hlo_t))
assert n_ar == 1, f"tab allreduce should be one op, got {n_ar}"
print("TAB_OK")
"""


@pytest.mark.slow
def test_tab_collectives_multi_device():
    src = str(Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT, src],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert "TAB_OK" in out.stdout, out.stderr[-3000:]
