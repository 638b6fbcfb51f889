"""Adapter from a dense GQA configuration file to the system under test:
``repro.models.transformer.DenseLM`` served by
``repro.runtime.serve.BatchedServer`` on the paged path, greedy.

The weights come from ``bench.refs.dense_gqa`` (drawn on the device from
the seed); with ``serving.weights_tier == "pinned_host"`` the layer stack
is placed in the remote tier and streamed one layer ahead by the
orchestrator's paged layer scan.
"""
from __future__ import annotations

import gc

import jax

from bench.refs import dense_gqa as ref


def program_config(config: dict):
    from repro.models.base import ModelConfig, PagerPolicy
    m, srv = config["model"], config["serving"]
    cfg = ModelConfig(
        name=config["name"], family="dense",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], head_dim=m["head_dim"], qkv_bias=True,
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        page_size=srv["page_size"], tp=1, remat=False,
        pager=PagerPolicy(enabled=srv["weights_tier"] == "pinned_host"))
    if (cfg.padded_heads, cfg.padded_kv_heads, cfg.padded_vocab) != (
            cfg.num_heads, cfg.num_kv_heads, cfg.vocab):
        raise ValueError(f"{config['name']}: the program would pad heads or "
                         f"vocabulary, so it would not run the published "
                         f"widths")
    return cfg


def build(config: dict, seed: int, log) -> tuple:
    """The model, its parameters as the program serves them, and the
    layer weights' memory kinds.  The layer stack is made first, and
    moved to the remote tier before the embedding and head are made, so
    the device never holds every weight at once in a remote-tier
    configuration."""
    from repro.configs import build_model
    from repro.memory import tiers
    cfg = program_config(config)
    model = build_model(cfg)
    s = ref.sizes(config)
    layers = ref.make_layers(s, seed)
    jax.block_until_ready(layers)
    if config["serving"]["weights_tier"] == "pinned_host":
        dev = jax.devices()[0]
        host = jax.sharding.SingleDeviceSharding(
            dev, memory_kind=tiers.resolved_remote_kind())
        on_device = layers
        layers = jax.device_put(on_device, host)
        jax.block_until_ready(layers)
        del on_device
        gc.collect()
        layers = model.mem.place_layer_weights(layers)
    params = dict(ref.make_outer(s, seed), layers=layers)
    jax.block_until_ready(params)
    kinds = sorted({x.sharding.memory_kind
                    for x in jax.tree.leaves(params["layers"])})
    log(f"layer weights' memory kinds {kinds}")
    return model, params, kinds


SERVING_KEYS = {"weights_tier", "slots", "max_seq", "page_size",
                "num_pages", "block_size"}


def server(model, params, config: dict, seed: int):
    """The server, greedy, on the paged path with bfloat16 KV; prefix
    caching and preemption stay off until a cell needs them."""
    from repro.runtime.serve import BatchedServer
    srv = config["serving"]
    unknown = set(srv) - SERVING_KEYS
    if unknown:
        raise ValueError(f"{config['name']}: serving keys the adapter does "
                         f"not read: {sorted(unknown)}")
    return BatchedServer(
        model, params, batch_size=srv["slots"], max_seq=srv["max_seq"],
        temperature=0.0, seed=seed & 0x7FFFFFFF,
        block_size=srv["block_size"], paged=True,
        page_size=srv["page_size"], num_pages=srv["num_pages"],
        prefix_cache=False, preempt=False)

