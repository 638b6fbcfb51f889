#!/usr/bin/env python3
"""Chip smoke test: FengHuang serving of qwen2.5-14b at its published
widths on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py             # one chip: phases a, b, c
    python chip_smoke.py --chips 4   # four chips: the tensor-parallel phase

One process, no children.  Weights are random from ``--seed``; every
width (d_model 5120, 40 query / 8 KV heads of 128, d_ff 13824, vocab
152064, QKV bias) is the published one.  One chip holds 16 of the 48
layers (the depth cut); four chips hold all 48 at tp=4.

Phases on one chip:

a. the paged decode kernel at serving widths against the gather oracle,
   for bf16, int8 and fp8 pools with ``extra_kv``;
c. ``BatchedServer`` (paged, greedy) serving 12 requests with the layer
   weights in the remote tier (pinned host memory), streamed one layer
   at a time by the paged layer scan — run before b, so the device's
   peak memory shows the weights never sat on it;
b. the same traffic with the weights resident.

Both b and c have every generated position's logits checked against a
float32 reference forward teacher-forced on that server's own tokens.
Their tokens are compared and the count printed, not asserted: the two
are differently compiled programs (weights streamed vs resident), whose
bf16 roundings differ in the last bit on the chip, and a random-weight
model's top-2 logit margin is often below that (the first chip run
found 8 of 12 requests diverging somewhere in 32 tokens).

Lines before the last are set-up facts (compile-inclusive wall seconds,
token counts, bytes), not performance metrics.  The last line of stdout
is one JSON object; a run that finds no TPU, or fails any check, exits
non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CONFIG_ID = "qwen2.5-14b"
ONE_CHIP_LAYERS = 16   # 16 x 0.55 GB of layers + 3.1 GB embed/head fit
#                        16 GB with the KV pool; 48 layers are 29.5 GB
SLOTS = 8
POOL_TOKENS = 2048     # KV pool per slot
NEW_TOKENS = 32
PROMPT_BUCKETS = (64, 256, 1024)   # 4 prompts each, order from --seed
SUBMIT_FIRST = 8       # the other 4 arrive once serving is under way

# Served logits vs the float32 reference.  The server runs bf16 (8
# significant bits, unit roundoff 2^-8): every layer rounds its matmul
# outputs and residual adds to bf16, so the hidden state reaching the
# head is off by ~1-2% of its norm, and the head's bf16 logits (standard
# deviation ~1, max ~5) round by up to 2^-6 more.  The same comparison
# on the CPU at reduced widths (d_model 512-1024, vocab 8-16k) gave a
# largest error of 0.077 at 16 layers and 0.125 at 48; the largest of
# 152k x 384 errors is ~1.2x the largest of 8k x 96.  0.25 leaves ~2x.
LOGIT_TOL = 0.25
# Kernel output vs the gather oracle: both compute the online softmax in
# float32; the kernel feeds the PV product bf16 probabilities (relative
# error 2^-8 each) and rounds its output to bf16 (2^-8 relative), so its
# error is at most 2 * 2^-8 * max|v|.
KERNEL_TOL_PER_MAX_V = 2 * 2.0 ** -8


def fact(msg: str) -> None:
    print(f"set-up: {msg}", flush=True)


def smoke_config(num_layers: int, tp: int):
    from repro.configs import get_config
    cfg = get_config(CONFIG_ID)
    return dataclasses.replace(cfg, num_layers=num_layers, tp=tp,
                               remat=False)


def traffic(vocab: int, seed: int) -> list[np.ndarray]:
    """12 prompts: four from each length bucket, in a seeded order."""
    rng = np.random.default_rng(seed)
    lens = rng.permutation(np.repeat(PROMPT_BUCKETS, 4))
    return [rng.integers(1, vocab, size=int(n), dtype=np.int32)
            for n in lens]


# ---------------------------------------------------------------------------
# phase a: kernel at real widths against the gather oracle
# ---------------------------------------------------------------------------

def phase_kernel(cfg, seed: int, *, batch: int = SLOTS,
                 pages_per_seq: int = 64) -> dict:
    """Runs the paged decode kernel and the gather oracle on the same
    random pool; returns {pool dtype: (max abs error, bound, compiled
    kernel text holds a Mosaic custom call)}."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.kernels.paged_attention.ref import paged_attention_ref, to_f32
    from repro.models.layers import kv_pool_quantize

    hkv, g, d, page = (cfg.padded_kv_heads, cfg.q_per_kv, cfg.head_dim,
                       cfg.page_size)
    n_pool = batch * pages_per_seq + 1
    key = jax.random.PRNGKey(seed)
    kq, kk, kv, k0, v0, kl = jax.random.split(key, 6)
    q = jax.random.normal(kq, (batch, hkv, g, d), jnp.bfloat16)
    kf = jax.random.normal(kk, (n_pool, hkv, page, d), jnp.float32)
    vf = jax.random.normal(kv, (n_pool, hkv, page, d), jnp.float32)
    extra = (jax.random.normal(k0, (batch, hkv, d), jnp.bfloat16),
             jax.random.normal(v0, (batch, hkv, d), jnp.bfloat16))
    rng = np.random.default_rng(seed)
    table = jnp.asarray(1 + rng.permutation(n_pool - 1).reshape(
        batch, pages_per_seq), jnp.int32)
    lens = jax.random.randint(kl, (batch,), 1, pages_per_seq * page + 1,
                              jnp.int32)
    out = {}
    for name in ("bf16", "int8", "fp8_e4m3"):
        if name == "bf16":
            kp, vp, scales = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16), {}
            v_deq = vp.astype(jnp.float32)
        else:
            qdt, qmax = dataclasses.replace(cfg, kv_dtype=name).KV_DTYPES[name]
            kp, ks = kv_pool_quantize(kf, qdt, qmax)
            vp, vs = kv_pool_quantize(vf, qdt, qmax)
            scales = {"k_scales": ks, "v_scales": vs}
            v_deq = to_f32(vp) * vs.astype(jnp.float32)[..., None]
        kernel = jax.jit(lambda *a, **kw: paged_ops.paged_attention(
            *a, extra_kv=extra, **kw))
        compiled = kernel.lower(q, kp, vp, table, lens, **scales).compile()
        got = compiled(q, kp, vp, table, lens, **scales)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(paged_attention_ref)(q, kp, vp, table, lens,
                                                extra, **scales)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        max_v = max(float(jnp.max(jnp.abs(v_deq))),
                    float(jnp.max(jnp.abs(extra[1].astype(jnp.float32)))))
        out[name] = (err, KERNEL_TOL_PER_MAX_V * max_v,
                     "tpu_custom_call" in compiled.as_text())
    return out


# ---------------------------------------------------------------------------
# weights: random from the seed, one layer at a time
# ---------------------------------------------------------------------------

def host_layers(model, key) -> dict:
    """The stacked layer weights as host (numpy) arrays, built one layer
    at a time on the device, so the whole stack is never on the device."""
    import jax
    init = jax.jit(model.init_layer)
    keys = jax.random.split(key, model.cfg.num_layers)
    stacked = None
    for i in range(model.cfg.num_layers):
        layer = jax.device_get(init(keys[i]))
        if stacked is None:
            stacked = jax.tree.map(
                lambda x: np.empty((model.cfg.num_layers,) + x.shape,
                                   x.dtype), layer)
        jax.tree.map(lambda buf, x: buf.__setitem__(i, x), stacked, layer)
    return stacked


def outer_params(model, key) -> dict:
    """Embedding, LM head and final norm, on the device."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    cfg = model.cfg
    return {"embed": jax.jit(lambda k: L.embed_params(k, cfg))(key),
            "ln_f": jnp.ones((cfg.d_model,), cfg.dtype)}


def tree_nbytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def memory_stat(dev, name: str) -> int:
    """One of the device allocator's counters (``bytes_in_use``,
    ``peak_bytes_in_use``)."""
    return dev.memory_stats()[name]


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve(model, params, prompts, *, record_logits: bool, mesh=None,
          deterministic: bool = True):
    """Serve ``prompts`` greedily; the last ``len - SUBMIT_FIRST`` are
    submitted after two decode blocks, so they are admitted mid-stream.
    Returns (requests, server)."""
    from repro.runtime.serve import BatchedServer
    server = BatchedServer(model, params, batch_size=SLOTS,
                           max_seq=POOL_TOKENS, paged=True, temperature=0.0,
                           mesh=mesh, deterministic=deterministic,
                           record_logits=record_logits)
    reqs = [server.submit(p, max_new_tokens=NEW_TOKENS)
            for p in prompts[:SUBMIT_FIRST]]
    server.run_once(max_blocks=2)
    reqs += [server.submit(p, max_new_tokens=NEW_TOKENS)
             for p in prompts[SUBMIT_FIRST:]]
    for _ in range(len(reqs)):
        if all(r.done.is_set() for r in reqs):
            break
        server.run_once()
    for r in reqs:
        if r.error is not None or len(r.output) != NEW_TOKENS:
            raise AssertionError(f"request {r.uid} ended {r.outcome}: "
                                 f"{r.error}, {len(r.output)} tokens")
    if server.mem.degraded:
        raise AssertionError(f"memory tiers degraded: {server.mem.degraded}")
    return reqs, server


# ---------------------------------------------------------------------------
# float32 reference forward, independent of the model code
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, pos, theta):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs     # (T, 1, half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _ref_block(x, lp, cfg):
    """One pre-norm decoder layer on one sequence x: (T, d) float32."""
    import jax
    import jax.numpy as jnp
    t = x.shape[0]
    a, m = lp["attn"], lp["mlp"]
    hd = cfg.head_dim
    hq, hkv = a["wq"].shape[1] // hd, a["wk"].shape[1] // hd
    pos = jnp.arange(t)
    h = _rms(x, lp["ln1"], cfg.norm_eps)
    q = _rope((h @ a["wq"] + a["bq"]).reshape(t, hq, hd), pos,
              cfg.rope_theta)
    k = _rope((h @ a["wk"] + a["bk"]).reshape(t, hkv, hd), pos,
              cfg.rope_theta)
    v = (h @ a["wv"] + a["bv"]).reshape(t, hkv, hd)
    q = q.reshape(t, hkv, hq // hkv, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / np.sqrt(hd)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                  s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(t, hq * hd) @ a["wo"]
    h = _rms(x, lp["ln2"], cfg.norm_eps)
    return x + (jax.nn.silu(h @ m["wg"]) * (h @ m["wi"])) @ m["wo"]


def reference_logits(cfg, params, tokens, at, *, vocab_chunks: int = 8):
    """float32 logits of a plain forward over ``tokens`` (N, T) at
    positions ``at`` (N, K), computed from the bf16 weights one layer at
    a time (no float32 copy of the model), at the highest matmul
    precision."""
    import jax
    import jax.numpy as jnp

    f32 = lambda tree: jax.tree.map(lambda w: w.astype(jnp.float32), tree)

    def forward(params, tokens, at):
        x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(
            jnp.float32)

        def layer(x, lp):
            lp = f32(lp)
            return jax.lax.map(lambda xs: _ref_block(xs, lp, cfg), x), None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = jnp.take_along_axis(x, at[..., None], axis=1)       # (N, K, d)
        x = _rms(x, params["ln_f"].astype(jnp.float32), cfg.norm_eps)
        head = params["embed"]["head"]
        d, v = head.shape
        chunks = head.reshape(d, vocab_chunks, v // vocab_chunks)
        logits = jax.lax.map(
            lambda c: x @ c.astype(jnp.float32),
            jnp.moveaxis(chunks, 1, 0))                # (C, N, K, V/C)
        return jnp.moveaxis(logits, 0, 2).reshape(x.shape[:2] + (v,))

    with jax.default_matmul_precision("highest"):
        return jax.jit(forward)(params, tokens, at)


def check_against_reference(cfg, params, prompts, reqs) -> dict:
    """Teacher-force the reference on each request's prompt + its own
    generated tokens; compare every generated position's logits."""
    import jax
    lens = [len(p) for p in prompts]
    width = max(lens) + NEW_TOKENS
    tokens = np.zeros((len(prompts), width), np.int32)
    at = np.zeros((len(prompts), NEW_TOKENS), np.int32)
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        seq = np.concatenate([p, np.asarray(r.output, np.int32)])
        tokens[i, :len(seq)] = seq
        # logits at position j predict token j + 1: the generated
        # tokens sit at positions len(p) .. len(p) + NEW_TOKENS - 1
        at[i] = len(p) - 1 + np.arange(NEW_TOKENS)
    ref = np.asarray(jax.device_get(reference_logits(
        cfg, params, tokens, at)))[..., :cfg.vocab]
    got = np.stack([np.stack(r.logits) for r in reqs]).astype(
        np.float32)[..., :cfg.vocab]
    err = np.abs(got - ref)
    top2 = np.sort(ref, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    decided = margin > LOGIT_TOL
    served = np.asarray([r.output for r in reqs])
    agree = served == ref.argmax(-1)
    return {"max_abs_err": float(err.max()),
            "mean_abs_err": float(err.mean()),
            "positions": int(err.shape[0] * err.shape[1]),
            "decided": int(decided.sum()),
            "decided_disagree": int((decided & ~agree).sum()),
            "argmax_agree": int(agree.sum())}


def assert_reference(check: dict, tag: str) -> None:
    fact(f"{tag} reference check {json.dumps(check)} (tolerance "
         f"{LOGIT_TOL})")
    if check["max_abs_err"] > LOGIT_TOL:
        raise AssertionError(f"{tag}: logits off the float32 reference by "
                             f"{check['max_abs_err']} > {LOGIT_TOL}")
    if check["decided_disagree"]:
        raise AssertionError(
            f"{tag}: {check['decided_disagree']} greedy tokens differ from "
            f"the reference argmax where its top-2 margin exceeds "
            f"{LOGIT_TOL}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_one_chip(cfg, seed: int, *, check_custom_call: bool = True) -> None:
    import jax
    from repro.configs import build_model
    from repro.memory import tiers
    from repro.models.base import PagerPolicy

    dev = jax.devices()[0]
    fact(f"config {cfg.name}: {cfg.num_layers} of 48 layers (depth cut), "
         f"d_model {cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}"
         f" (padded {cfg.padded_heads}/{cfg.padded_kv_heads}), d_ff "
         f"{cfg.d_ff}, vocab {cfg.vocab}, tp {cfg.tp}")

    # a. kernel vs oracle
    t0 = time.perf_counter()
    kernel = phase_kernel(cfg, seed)
    for name, (err, bound, custom) in kernel.items():
        fact(f"phase a {name} pool: kernel vs oracle max abs err {err} "
             f"(bound {bound}), Mosaic custom call {custom}")
        if not err <= bound:
            raise AssertionError(f"kernel off the oracle ({name}): {err}")
        if check_custom_call and not custom:
            raise AssertionError(f"{name}: kernel executable has no "
                                 f"tpu_custom_call")
    fact(f"phase a wall seconds incl. compile {time.perf_counter() - t0}")

    key_layers, key_outer = jax.random.split(jax.random.PRNGKey(seed))
    prompts = traffic(cfg.vocab, seed)
    fact(f"prompt lengths {[len(p) for p in prompts]}, {NEW_TOKENS} new "
         f"tokens each, {len(prompts) - SUBMIT_FIRST} submitted mid-stream")
    model = build_model(cfg)
    t0 = time.perf_counter()
    layers_np = host_layers(model, key_layers)
    outer = outer_params(model, key_outer)
    layer_bytes = tree_nbytes(layers_np)
    resident_bytes = layer_bytes + tree_nbytes(outer)
    fact(f"weights from seed {seed}: {layer_bytes} layer bytes, "
         f"{resident_bytes} if resident, built in {time.perf_counter() - t0}"
         f" s")

    # c. remote tier: layer weights in pinned host memory, streamed
    t0 = time.perf_counter()
    paged_model = build_model(dataclasses.replace(
        cfg, pager=PagerPolicy(enabled=True)))
    host = jax.sharding.SingleDeviceSharding(
        dev, memory_kind=tiers.resolved_remote_kind())
    layers_host = paged_model.mem.place_layer_weights(
        jax.device_put(layers_np, host))
    kinds = {x.sharding.memory_kind for x in jax.tree.leaves(layers_host)}
    remote = paged_model.mem.ledger.classes(tiers.REMOTE).get(
        "layer_weights")
    reqs_c, server_c = serve(paged_model, dict(outer, layers=layers_host),
                             prompts, record_logits=True)
    peak_c = memory_stat(dev, "peak_bytes_in_use")
    fact(f"phase c: layer weights in {sorted(kinds)}, ledger remote "
         f"layer_weights {remote} bytes, device peak {peak_c} bytes vs "
         f"{resident_bytes} if resident, {server_c.stats['tokens']} tokens, "
         f"wall seconds incl. compile {time.perf_counter() - t0}")
    if kinds != {"pinned_host"}:
        raise AssertionError(f"layer weights not in pinned_host: {kinds}")
    if remote != layer_bytes:
        raise AssertionError(f"ledger remote layer bytes {remote} != "
                             f"{layer_bytes}")
    if not peak_c < resident_bytes:
        raise AssertionError(f"device peak {peak_c} >= weights-if-resident "
                             f"{resident_bytes}: weights reached the device")
    tokens_c = [r.output for r in reqs_c]
    del server_c, layers_host
    gc.collect()        # the server's jitted closures hold it in a cycle

    # b. resident weights, logits against the float32 reference
    t0 = time.perf_counter()
    params = dict(outer, layers=jax.device_put(layers_np, dev))
    del layers_np
    reqs_b, server_b = serve(model, params, prompts, record_logits=True)
    fact(f"phase b: {server_b.stats['tokens']} tokens, "
         f"{server_b.stats['compiles']} executables, wall seconds incl. "
         f"compile {time.perf_counter() - t0}")
    if check_custom_call:
        with server_b._mesh_ctx():
            text = server_b._decode_loop.lower(
                server_b.params, server_b.cache,
                server_b.state).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError("served decode executable has no "
                                 "tpu_custom_call: the kernel did not run")
    tokens_b = [r.output for r in reqs_b]
    fact(f"remote-tier tokens equal resident tokens in "
         f"{sum(a == b for a, b in zip(tokens_b, tokens_c))} of "
         f"{len(tokens_b)} requests")
    del server_b
    gc.collect()
    t0 = time.perf_counter()
    assert_reference(check_against_reference(cfg, params, prompts, reqs_b),
                     "phase b")
    assert_reference(check_against_reference(cfg, params, prompts, reqs_c),
                     "phase c")
    fact(f"reference wall seconds incl. compile {time.perf_counter() - t0}")


def run_four_chips(cfg, seed: int) -> None:
    """48 layers at tp=4 over a (1, 4) mesh, parameters initialised
    already sharded (never whole on one device)."""
    import jax
    from repro.configs import build_model
    from repro.launch.mesh import make_serving_mesh
    from repro.runtime.sharding import activate_mesh, named_shardings

    mesh = make_serving_mesh(model=len(jax.devices()))
    model = build_model(cfg)
    fact(f"config {cfg.name}: {cfg.num_layers} layers, tp {cfg.tp}, mesh "
         f"{dict(mesh.shape)}; parameters in the training layout (every "
         f"projection sharded), so tokens need not match one device's")
    t0 = time.perf_counter()
    shardings = named_shardings(model.param_specs(), mesh)
    params = jax.jit(model.init, out_shardings=shardings)(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    fact(f"sharded init seconds incl. compile {time.perf_counter() - t0}")
    prompts = traffic(cfg.vocab, seed)
    t0 = time.perf_counter()
    reqs, server = serve(model, params, prompts, record_logits=True,
                         mesh=mesh, deterministic=False)
    in_use = [memory_stat(d, "bytes_in_use") for d in jax.devices()]
    fact(f"served {server.stats['tokens']} tokens, wall seconds incl. "
         f"compile {time.perf_counter() - t0}; bytes_in_use per device "
         f"{in_use}")
    if max(in_use) > 1.1 * min(in_use):
        raise AssertionError(f"device bytes differ by more than 10%: "
                             f"{in_use}")
    del server
    gc.collect()
    t0 = time.perf_counter()
    with activate_mesh(mesh):
        check = check_against_reference(cfg, params, prompts, reqs)
    assert_reference(check, "four chips")
    fact(f"reference wall seconds incl. compile {time.perf_counter() - t0}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import cache_entries, enable_compile_cache
    cache = enable_compile_cache()
    fact(f"compile cache {cache}, {cache_entries(cache)} entries before")
    if args.chips == 1:
        run_one_chip(smoke_config(ONE_CHIP_LAYERS, tp=1), args.seed)
    else:
        run_four_chips(smoke_config(48, tp=4), args.seed)
    fact(f"compile cache {cache_entries(cache)} entries after")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
