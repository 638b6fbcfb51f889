"""Compile rehearsals for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes off
Mosaic's (8, 128) tiling, programs that do not fit): these tests compile
the serving hot path at qwen2.5-14b's published widths for one v5e chip,
so such a fault fails here instead of on the chip.  The topology is
described inside a fixture — never while a module is imported — because
only one process at a time may load the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from bench import window
from repro.configs import build_model, get_config
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.kernel import paged_attention
from repro.models.base import DecodeState
from repro.runtime.serve import make_decode_loop
from repro.runtime.sharding import activate_mesh

# serving widths of qwen2.5-14b at tp=1: 8 KV heads, 5 query heads each
B, HKV, G, D, PAGE, PAGES_PER_SEQ = 8, 8, 5, 128, 16, 64
# the benchmark cell's decode batch and the table widths it runs at
CELL_SLOTS, CELL_WIDTHS = 32, (64, 256)
# KV heads of one shard when qwen2.5-14b is served at tp=4
TP4_SHARD_HKV = HKV // 4
POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8,
               "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def v5e_chips():
    """The four chips of a described v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(v5e_chips):
    return SingleDeviceSharding(v5e_chips[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(compiled) -> list[str]:
    """The compiled module's Mosaic calls as the profiler names them:
    each operand written with its shape in front, as
    ``bench/window.KERNEL`` expects."""
    text = compiled.as_text()
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (\S+) ", text, re.M))

    def with_shapes(m):
        names = re.sub(r"/\*index=\d+\*/", "", m.group(1)).split(", ")
        return "custom-call(" + ", ".join(
            f"{shapes.get(n, '?')} {n}" for n in names) + ")"

    return [re.sub(r"custom-call\(([^)]*)\)", with_shapes, ln)
            for ln in text.splitlines() if "tpu_custom_call" in ln]


def _assert_one_kernel(compiled, slots: int, width: int) -> None:
    """One custom call, found by the benchmark's pattern, whose first
    two operands are the ``s32[B, W]`` table and the ``s32[B]``
    lengths."""
    calls = _kernel_calls(compiled)
    assert len(calls) == 1
    assert re.search(window.KERNEL, calls[0])
    assert re.search(rf"custom-call\(s32\[{slots},{width}\]\S* \S+, "
                     rf"s32\[{slots}\]", calls[0])


@pytest.mark.parametrize("extra", [False, True])
@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
@pytest.mark.parametrize("width,hkv", [
    pytest.param(w, HKV, id=str(w)) for w in CELL_WIDTHS] + [
    pytest.param(CELL_WIDTHS[-1], TP4_SHARD_HKV, id="tp4-shard")])
def test_paged_kernel_compiles_for_v5e(one_chip, width, hkv, pool, extra):
    """The kernel at the cell's batch and table widths, for every pool
    dtype, and at the KV heads one shard holds under tp=4; the
    benchmark still finds it as one custom call whose first two
    operands are the ``s32[B, W]`` table and the ``s32[B]`` lengths."""
    dt = POOL_DTYPES[pool]
    n_pool = CELL_SLOTS * width + 1
    s = lambda shape, dtype: _shape(one_chip, shape, dtype)
    args = [s((CELL_SLOTS, hkv, G, D), jnp.bfloat16),
            s((n_pool, hkv, PAGE, D), dt), s((n_pool, hkv, PAGE, D), dt),
            s((CELL_SLOTS, width), jnp.int32), s((CELL_SLOTS,), jnp.int32)]
    kw = {}
    if dt != jnp.bfloat16:
        kw["k_scales"] = kw["v_scales"] = s((n_pool, hkv, PAGE),
                                            jnp.bfloat16)
    if extra:
        kw["extra_kv"] = (s((CELL_SLOTS, hkv, D), jnp.bfloat16),) * 2
    compiled = jax.jit(paged_attention).lower(*args, **kw).compile()
    _assert_one_kernel(compiled, CELL_SLOTS, width)


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_sharded_paged_kernel_compiles_for_v5e(v5e_chips, pool):
    """``sharded_paged_attention`` as the tp=4 server calls it: over a
    (1, 4) mesh of v5e chips, each running the kernel on its own
    ``HKV / 4`` heads inside ``shard_map`` with the pools in HBM."""
    mesh = Mesh(np.array(v5e_chips).reshape(1, 4), ("data", "model"))
    dt, width = POOL_DTYPES[pool], CELL_WIDTHS[-1]
    n_pool = CELL_SLOTS * width + 1
    s = lambda shape, dtype, spec=P(None, "model"): jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec))
    args = [s((CELL_SLOTS, HKV, G, D), jnp.bfloat16),
            s((n_pool, HKV, PAGE, D), dt), s((n_pool, HKV, PAGE, D), dt),
            s((CELL_SLOTS, width), jnp.int32, P()),
            s((CELL_SLOTS,), jnp.int32, P())]
    kw = {"extra_kv": (s((CELL_SLOTS, HKV, D), jnp.bfloat16),) * 2}
    if dt != jnp.bfloat16:
        kw["k_scales"] = kw["v_scales"] = s((n_pool, HKV, PAGE),
                                            jnp.bfloat16)
    with activate_mesh(mesh):
        compiled = jax.jit(paged_ops.sharded_paged_attention).lower(
            *args, **kw).compile()
    _assert_one_kernel(compiled, CELL_SLOTS, width)


def test_decode_block_compiles_for_v5e(one_chip, monkeypatch):
    """The fused decode block (``make_decode_loop``) of the published-
    width config at 2 layers, with the paged kernel selected as on a
    TPU."""
    monkeypatch.setattr(paged_ops, "use_pallas_kernel", lambda: True)
    cfg = dataclasses.replace(get_config("qwen2.5-14b"), num_layers=2,
                              tp=1, remat=False)
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: model.init_paged_cache(B * PAGES_PER_SEQ + 1, PAGE)))
    s = lambda shape, dtype: _shape(one_chip, shape, dtype)
    state = DecodeState(
        tokens=s((B, 1), jnp.int32), pos=s((B,), jnp.int32),
        active=s((B,), bool), remaining=s((B,), jnp.int32),
        key=s((2,), jnp.uint32), pages=s((B, PAGES_PER_SEQ), jnp.int32),
        slot_keys=s((B, 2), jnp.uint32))
    loop = make_decode_loop(model, block_size=8, detect_nonfinite=True)
    compiled = loop.lower(params, cache, state).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # two layers of weights, embedding and head, and the pool fit a chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
