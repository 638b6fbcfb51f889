"""Plain reference for a dense pre-norm decoder with grouped-query
attention, QKV bias, RoPE and a SwiGLU MLP (the Qwen2 block), and the
generator of its random weights.

Nothing here imports the system under test.  The weights are drawn
on the device from the run's seed in one jitted call; the benchmark hands
them to the program, and the reference draws them again from the same
seed once the program's state is freed, so it never reads an array the
program holds.

The forward pass is the published block written out in float32 at the
highest matmul precision (a TPU otherwise runs float32 matmuls in
bfloat16 passes), one layer at a time and one sequence at a time, so
that it fits beside the weights.  ``precision="fp8"`` is the control:
the same forward with every matmul operand rounded to float8_e4m3
(per-row scales for activations, per-column scales for weights), the
step below the configuration's bfloat16 that a later change could be
tempted to take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
BIAS_STD = 0.1          # Qwen2 projections carry a learned QKV bias
NORM_STD = 0.05         # norm scales: 1 + N(0, NORM_STD)


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    c = config["model"]
    return {"d": c["hidden_size"], "dff": c["intermediate_size"],
            "hq": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
            "hd": c["head_dim"], "layers": c["num_hidden_layers"],
            "vocab": c["vocab_size"], "theta": float(c["rope_theta"]),
            "eps": float(c["rms_norm_eps"])}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed below 2**64 (JAX's own constructor takes
    32 bits)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def _layer(key, s: dict) -> dict:
    d, dff, hq, hkv, hd = s["d"], s["dff"], s["hq"], s["hkv"], s["hd"]
    k = jax.random.split(key, 12)
    bf = jnp.bfloat16

    def mat(kk, shape):
        return (jax.random.normal(kk, shape, jnp.float32)
                / np.sqrt(shape[0])).astype(bf)

    def vec(kk, n, std, mean=0.0):
        return (mean + std * jax.random.normal(kk, (n,), jnp.float32)
                ).astype(bf)

    return {
        "attn": {"wq": mat(k[0], (d, hq * hd)), "wk": mat(k[1], (d, hkv * hd)),
                 "wv": mat(k[2], (d, hkv * hd)), "wo": mat(k[3], (hq * hd, d)),
                 "bq": vec(k[4], hq * hd, BIAS_STD),
                 "bk": vec(k[5], hkv * hd, BIAS_STD),
                 "bv": vec(k[6], hkv * hd, BIAS_STD)},
        "mlp": {"wi": mat(k[7], (d, dff)), "wg": mat(k[8], (d, dff)),
                "wo": mat(k[9], (dff, d))},
        "ln1": vec(k[10], d, NORM_STD, 1.0),
        "ln2": vec(k[11], d, NORM_STD, 1.0),
    }


def make_layers(s: dict, seed: int) -> dict:
    """The stacked (L, ...) layer weights, bfloat16, on the device, in
    one jitted call (a scan, so a layer's float32 draws are the only
    temporaries)."""
    key = jax.random.fold_in(seed_key(seed), 1)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, s["layers"])
        return jax.lax.map(lambda kk: _layer(kk, s), keys)
    return build(key)


def make_outer(s: dict, seed: int) -> dict:
    """Embedding, LM head and final norm, bfloat16, on the device."""
    key = jax.random.fold_in(seed_key(seed), 2)

    @jax.jit
    def build(key):
        k1, k2, k3 = jax.random.split(key, 3)
        tok = jax.random.normal(k1, (s["vocab"], s["d"]), jnp.float32)
        head = (jax.random.normal(k2, (s["d"], s["vocab"]), jnp.float32)
                / np.sqrt(s["d"]))
        ln_f = 1.0 + NORM_STD * jax.random.normal(k3, (s["d"],), jnp.float32)
        return {"embed": {"tok": tok.astype(jnp.bfloat16),
                          "head": head.astype(jnp.bfloat16)},
                "ln_f": ln_f.astype(jnp.bfloat16)}
    return build(key)


def make_weights(s: dict, seed: int) -> dict:
    return dict(make_outer(s, seed), layers=make_layers(s, seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _q8(x, axis):
    """Round x to float8_e4m3 with one scale per slice along ``axis``
    (the reduction axis of the matmul it feeds); returns the dequantized
    float32 values."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(x, w, precision: str):
    if precision == "fp8":
        return _q8(x, -1) @ _q8(w, 0)
    return x @ w


def _rms(x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs     # (T, 1, half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _block(x, lp, s, precision):
    """One pre-norm decoder layer on one sequence x: (T, d) float32."""
    t, hd, hq, hkv = x.shape[0], s["hd"], s["hq"], s["hkv"]
    a, m = lp["attn"], lp["mlp"]
    mm = functools.partial(_mm, precision=precision)
    pos = jnp.arange(t)
    h = _rms(x, lp["ln1"], s["eps"])
    q = _rope((mm(h, a["wq"]) + a["bq"]).reshape(t, hq, hd), pos, s["theta"])
    k = _rope((mm(h, a["wk"]) + a["bk"]).reshape(t, hkv, hd), pos, s["theta"])
    v = (mm(h, a["wv"]) + a["bv"]).reshape(t, hkv, hd)
    q = q.reshape(t, hkv, hq // hkv, hd)
    causal = pos[None, :, None] >= pos[None, None, :]

    def head(args):             # one KV head and its query group
        qh, kh, vh = args       # (t, g, hd), (t, hd), (t, hd)
        sc = jnp.einsum("tgd,sd->gts", qh, kh) / np.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        return jnp.einsum("gts,sd->tgd", jax.nn.softmax(sc, -1), vh)

    o = jax.lax.map(head, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                           v.transpose(1, 0, 2)))          # (hkv, t, g, hd)
    x = x + mm(o.transpose(1, 0, 2, 3).reshape(t, hq * hd), a["wo"])
    h = _rms(x, lp["ln2"], s["eps"])
    return x + mm(jax.nn.silu(mm(h, m["wg"])) * mm(h, m["wi"]), m["wo"])


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def _hidden(params, tokens, at, *, s, precision):
    """Final-norm hidden states of ``tokens`` (N, T) at positions ``at``
    (N, K)."""
    f32 = lambda tree: jax.tree.map(lambda w: w.astype(jnp.float32), tree)
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)

    def layer(x, lp):
        lp = f32(lp)
        return jax.lax.map(lambda xs: _block(xs, lp, s, precision), x), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = jnp.take_along_axis(x, at[..., None], axis=1)          # (N, K, d)
    return _rms(x, params["ln_f"].astype(jnp.float32), s["eps"])


@functools.partial(jax.jit, static_argnames=("precision", "chunks"))
def _logit_reduce(x, head, targets, *, precision, chunks):
    """Over the vocabulary, in ``chunks`` slices of the head: each
    position's largest logit, its logit of ``targets`` (N, K), and its
    argmax."""
    d, v = head.shape
    cw = v // chunks

    def chunk(carry, c):
        best, arg, tgt = carry
        w = jax.lax.dynamic_slice_in_dim(head, c * cw, cw, 1).astype(
            jnp.float32)
        lg = _mm(x, w, precision)                               # (N, K, cw)
        cbest = lg.max(-1)
        carg = lg.argmax(-1).astype(jnp.int32) + c * cw
        arg = jnp.where(cbest > best, carg, arg)
        best = jnp.maximum(best, cbest)
        local = targets - c * cw
        inside = (local >= 0) & (local < cw)
        got = jnp.take_along_axis(lg, jnp.clip(local, 0, cw - 1)[..., None],
                                  -1)[..., 0]
        tgt = jnp.where(inside, got, tgt)
        return (best, arg, tgt), None

    init = (jnp.full(targets.shape, -jnp.inf, jnp.float32),
            jnp.zeros(targets.shape, jnp.int32),
            jnp.zeros(targets.shape, jnp.float32))
    (best, arg, tgt), _ = jax.lax.scan(chunk, init, jnp.arange(chunks))
    return best, arg, tgt


def logit_table(s: dict, params, tokens: np.ndarray, at: np.ndarray,
                targets: np.ndarray, *, precision: str = "float32",
                chunks: int = 8):
    """(best, argmax, logit of ``targets``) at each position ``at``,
    as numpy arrays (N, K)."""
    v = params["embed"]["head"].shape[1]
    while v % chunks:
        chunks //= 2
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, jnp.asarray(tokens), jnp.asarray(at),
                    s=_frozen(s), precision=precision)
        out = _logit_reduce(x, params["embed"]["head"], jnp.asarray(targets),
                            precision=precision, chunks=chunks)
    return tuple(np.asarray(jax.device_get(o)) for o in out)


class _frozen(dict):
    """A hashable dict, so sizes can be a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
