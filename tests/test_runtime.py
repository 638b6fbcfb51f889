"""Runtime: optimizer schedules, grad accumulation, compression,
checkpointing (incl. elastic restore), data pipeline, fault tolerance."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, build_model
from repro.data.pipeline import (ByteFileLM, DataConfig, PrefetchingLoader,
                                 SyntheticLM, pack_documents)
from repro.runtime import checkpoint, optim
from repro.runtime.ft import FTConfig, FaultTolerantLoop, StragglerMonitor
from repro.runtime.train import TrainConfig, make_train_step

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_wsd_schedule_shape():
    cfg = optim.AdamWConfig(lr=1.0, schedule="wsd", warmup_steps=10,
                            total_steps=100, decay_fraction=0.2)
    warm = optim.schedule_value(cfg, jnp.asarray(5))
    stable = optim.schedule_value(cfg, jnp.asarray(50))
    decay = optim.schedule_value(cfg, jnp.asarray(99))
    assert float(warm) == pytest.approx(0.5)
    assert float(stable) == pytest.approx(1.0)
    assert float(decay) < 0.15    # ~0.1x at the end (MiniCPM decay)


def test_cosine_schedule_endpoints():
    cfg = optim.AdamWConfig(lr=2.0, schedule="cosine", warmup_steps=10,
                            total_steps=100)
    assert float(optim.schedule_value(cfg, jnp.asarray(10))) == \
        pytest.approx(2.0, rel=0.05)
    assert float(optim.schedule_value(cfg, jnp.asarray(100))) == \
        pytest.approx(0.0, abs=1e-3)


def test_grad_accumulation_equivalence():
    """accum_steps=2 equals accum_steps=1 (same effective batch)."""
    cfg = get_config("whisper-base").reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype=jnp.float32, remat=False)
    model = build_model(cfg)
    params = model.init(KEY)
    opt = optim.init_opt_state(params)
    tokens = jax.random.randint(KEY, (4, 16), 0, cfg.vocab)
    frames = jax.random.normal(KEY, (4, cfg.encoder_seq, cfg.d_model))
    batch = {"tokens": tokens, "labels": tokens, "frames": frames}
    # AdamW's first step moves each weight by lr * g / (|g| + eps): where
    # |g| is near eps, the rounding of g (which accumulation reorders) is
    # amplified ~lr / eps.  eps = 1e-6 keeps every update a smooth
    # function of its gradient; the gradients here reach 1e-8.
    acfg = optim.AdamWConfig(lr=1e-2, total_steps=10, warmup_steps=0,
                             eps=1e-6)
    s1 = jax.jit(make_train_step(model, TrainConfig(adamw=acfg,
                                                    accum_steps=1)))
    s2 = jax.jit(make_train_step(model, TrainConfig(adamw=acfg,
                                                    accum_steps=2)))
    p1, _, m1 = s1(params, opt, batch)
    p2, _, m2 = s2(params, opt, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-4, rtol=1e-3)


@given(scale=st.floats(min_value=1e-4, max_value=1e3))
@settings(max_examples=20, deadline=None)
def test_int8_compression_error_feedback(scale):
    g = jnp.asarray(np.random.RandomState(0).randn(64) * scale, jnp.float32)
    err = jnp.zeros_like(g)
    deq, err2 = optim.compressed_grad(g, err)
    # dequantized + residual error reconstructs the gradient exactly
    np.testing.assert_allclose(np.asarray(deq + err2), np.asarray(g),
                               rtol=1e-5, atol=1e-6 * scale)
    # quantization error bounded by the int8 step
    assert float(jnp.abs(err2).max()) <= float(jnp.abs(g).max()) / 127.0 + 1e-9


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_gc():
    tree = {"a": jnp.arange(6.0).reshape(2, 3).astype(jnp.bfloat16),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        for step in (1, 2, 3, 4, 5):
            checkpoint.save(d, step, tree, keep=2)
        assert checkpoint.latest_step(d) == 5
        restored, step = checkpoint.restore(d, tree)
        assert step == 5
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        # GC kept only 2
        from pathlib import Path
        assert len(list(Path(d).glob("step_*"))) == 2


def test_checkpoint_async():
    tree = {"w": jnp.ones((8, 8))}
    with tempfile.TemporaryDirectory() as d:
        t = checkpoint.save_async(d, 7, tree)
        t.join(timeout=30)
        restored, step = checkpoint.restore(d, tree)
        assert step == 7


def test_elastic_restore_onto_mesh():
    """Restore re-shards for a (new) mesh — the elastic-scaling path."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_smoke_mesh
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save(d, 1, tree)
        mesh = make_smoke_mesh()
        restored, _ = checkpoint.restore(
            d, tree, mesh=mesh, specs={"w": P(None, "model")})
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding.mesh.shape["model"] == 1


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_determinism():
    cfg = DataConfig(batch=2, seq=8, vocab=64, seed=3)
    a = SyntheticLM(cfg).batch_at(5)
    b = SyntheticLM(cfg).batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticLM(cfg).batch_at(6)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_byte_file_dataset(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_text("hello world, this is a tiny corpus for testing!" * 10)
    cfg = DataConfig(batch=2, seq=16, vocab=256)
    ds = ByteFileLM(p, cfg)
    b = ds.batch_at(0)
    assert b["tokens"].shape == (2, 16)
    assert b["tokens"].max() < 256


@given(lens=st.lists(st.integers(1, 50), min_size=1, max_size=10),
       seq=st.integers(4, 64))
@settings(max_examples=25, deadline=None)
def test_packing_conserves_tokens(lens, seq):
    docs = [np.arange(1, n + 1, dtype=np.int32) for n in lens]
    packed = pack_documents(docs, seq)
    total = sum(lens)
    assert packed.shape[1] == seq
    # all real tokens present (pad id 0 never used by docs)
    assert (packed > 0).sum() == total


def test_prefetch_loader_order():
    cfg = DataConfig(batch=2, seq=8, vocab=64, prefetch=3)
    src = SyntheticLM(cfg)
    loader = PrefetchingLoader(src, cfg)
    try:
        for i in range(5):
            got = next(loader)
            np.testing.assert_array_equal(got["tokens"],
                                          src.batch_at(i)["tokens"])
    finally:
        loader.close()


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_ft_loop_restart_from_checkpoint():
    with tempfile.TemporaryDirectory() as d:
        fail = {7}

        def step_fn(state, i):
            if i in fail:
                fail.clear()
                raise RuntimeError("injected")
            return state + 1, {"loss": float(state)}

        loop = FaultTolerantLoop(
            FTConfig(ckpt_dir=d, ckpt_every=3, async_save=False), step_fn)
        state, end = loop.run(jnp.asarray(0.0), start_step=0, num_steps=10)
        assert loop.restarts == 1
        assert end == 10
        # replayed steps 6..9 after restore at 6 => state counts all steps
        assert float(state) == 10.0


def test_ft_loop_degrade_hook():
    calls = []

    def step_fn(state, i):
        raise RuntimeError("always fails")

    def degrade():
        calls.append(1)
        raise KeyboardInterrupt   # escape the loop for the test

    with tempfile.TemporaryDirectory() as d:
        loop = FaultTolerantLoop(
            FTConfig(ckpt_dir=d, max_restarts=2, async_save=False),
            step_fn, on_degrade=degrade)
        with pytest.raises(KeyboardInterrupt):
            loop.run(0, num_steps=5)
    assert calls == [1]


def test_straggler_monitor():
    mon = StragglerMonitor(factor=3.0)
    for _ in range(10):
        assert not mon.observe(1.0)
    assert mon.observe(10.0)
    assert mon.flags == 1
