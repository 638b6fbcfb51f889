"""Train a ~100M-parameter MiniCPM-family model for a few hundred steps
with the full production stack: WSD schedule, gradient accumulation,
fault-tolerant loop with checkpoints, prefetching data pipeline.

    PYTHONPATH=src python examples/train_minicpm.py --steps 300
"""
import argparse
import dataclasses
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, build_model
from repro.launch.compile_cache import enable_compile_cache
from repro.data.pipeline import DataConfig, PrefetchingLoader, SyntheticLM
from repro.runtime import optim
from repro.runtime.ft import FTConfig, FaultTolerantLoop
from repro.runtime.train import TrainConfig, make_train_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=384)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    # ~100M-parameter MiniCPM-family config (WSD schedule per the paper)
    cfg = get_config("minicpm-2b").reduced(
        num_layers=args.layers, d_model=args.d_model,
        num_heads=6, num_kv_heads=6, d_ff=args.d_model * 4, vocab=32768,
        head_dim=args.d_model // 6)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] minicpm-family: {n/1e6:.1f}M params, "
          f"{cfg.num_layers}L d={cfg.d_model}")

    tcfg = TrainConfig(
        adamw=optim.AdamWConfig(lr=6e-3, schedule="wsd", warmup_steps=20,
                                total_steps=args.steps, decay_fraction=0.2),
        accum_steps=2)
    step_fn = jax.jit(make_train_step(model, tcfg))
    opt = optim.init_opt_state(params)
    dcfg = DataConfig(batch=args.batch, seq=args.seq, vocab=cfg.vocab)
    loader = PrefetchingLoader(SyntheticLM(dcfg), dcfg)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="minicpm_ckpt_")
    losses = []

    def ft_step(state, i):
        p, o = state
        batch = {k: jnp.asarray(v) for k, v in next(loader).items()}
        p, o, m = step_fn(p, o, batch)
        losses.append(float(m["loss"]))
        if i % 25 == 0:
            print(f"[train] step {i:4d} loss {losses[-1]:.4f} "
                  f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.2f}")
        return (p, o), m

    loop = FaultTolerantLoop(
        FTConfig(ckpt_dir=ckpt_dir, ckpt_every=100, async_save=True), ft_step)
    try:
        (params, opt), end = loop.run((params, opt), num_steps=args.steps)
    finally:
        loader.close()
    print(f"[train] done at step {end}; loss {np.mean(losses[:10]):.3f} -> "
          f"{np.mean(losses[-10:]):.3f}; checkpoints in {ckpt_dir}; "
          f"straggler flags {loop.monitor.flags}, "
          f"backup batches {loader.backup_batches}")
    assert np.mean(losses[-10:]) < np.mean(losses[:10])
    print("[train] OK")


if __name__ == "__main__":
    main()
