"""Quickstart: build an assigned architecture, run a forward pass, train a
few steps, then serve it — all on CPU with a reduced config.

    PYTHONPATH=src python examples/quickstart.py [--arch qwen3-14b]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, build_model
from repro.launch.compile_cache import enable_compile_cache
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.runtime import optim
from repro.runtime.serve import BatchedServer
from repro.runtime.train import TrainConfig, make_train_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    print(f"[quickstart] {args.arch} (reduced): {cfg.num_layers}L "
          f"d={cfg.d_model} heads={cfg.num_heads} vocab={cfg.vocab}")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[quickstart] {n_params/1e6:.2f}M parameters")

    # --- forward ---
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    extra = {}
    if cfg.family == "encdec":
        extra["frames"] = jnp.zeros((2, cfg.encoder_seq, cfg.d_model))
    if cfg.family == "vlm":
        extra["patches"] = jnp.zeros((2, cfg.num_patches, cfg.d_model))
    logits = jax.jit(lambda p, t: model.forward(p, t, extra or None))(
        params, tokens)
    print(f"[quickstart] forward: logits {logits.shape}")

    # --- train a few steps ---
    tcfg = TrainConfig(adamw=optim.AdamWConfig(
        lr=3e-3, warmup_steps=2, total_steps=max(args.steps, 4)))
    step = jax.jit(make_train_step(model, tcfg))
    opt = optim.init_opt_state(params)
    data = SyntheticLM(DataConfig(batch=4, seq=32, vocab=cfg.vocab))
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        batch.update(extra)
        params, opt, m = step(params, opt, batch)
        if i % 2 == 0 or i == args.steps - 1:
            print(f"[quickstart] step {i}: loss {float(m['loss']):.4f} "
                  f"lr {float(m['lr']):.2e}")

    # --- serve (decoder families) ---
    if cfg.family not in ("encdec",):
        server = BatchedServer(model, params, batch_size=2, max_seq=64)
        req = server.submit(np.asarray([1, 2, 3], np.int32),
                            max_new_tokens=8)
        server.run_once()
        print(f"[quickstart] served tokens: {req.output}")
    print("[quickstart] OK")


if __name__ == "__main__":
    main()
