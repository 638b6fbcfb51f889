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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import build_model, get_config
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.kernel import paged_attention
from repro.models.base import DecodeState
from repro.runtime.serve import make_decode_loop

# serving widths of qwen2.5-14b at tp=1: 8 KV heads, 5 query heads each
B, HKV, G, D, PAGE, PAGES_PER_SEQ = 8, 8, 5, 128, 16, 64
POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8,
               "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("extra", [False, True])
@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_paged_kernel_compiles_for_v5e(one_chip, pool, extra):
    dt = POOL_DTYPES[pool]
    n_pool = B * PAGES_PER_SEQ + 1
    s = lambda shape, dtype: _shape(one_chip, shape, dtype)
    args = [s((B, HKV, G, D), jnp.bfloat16),
            s((n_pool, HKV, PAGE, D), dt), s((n_pool, HKV, PAGE, D), dt),
            s((B, PAGES_PER_SEQ), jnp.int32), s((B,), jnp.int32)]
    kw = {}
    if dt != jnp.bfloat16:
        kw["k_scales"] = kw["v_scales"] = s((n_pool, HKV, PAGE),
                                            jnp.bfloat16)
    if extra:
        kw["extra_kv"] = (s((B, HKV, D), jnp.bfloat16),) * 2
    text = jax.jit(paged_attention).lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in text


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
