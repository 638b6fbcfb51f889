"""Mixture-of-Experts LM (moonshot-v1-16b-a3b: 64e top-6; granite-moe-3b:
40e top-8 padded to 48) with expert parallelism over the model axis.

Dispatch is GShard-style capacity-based scatter/gather:

    route -> top_k -> position-in-expert (cumsum) -> scatter to (E, C, d)
    -> grouped expert GEMMs (E-sharded = expert parallelism) -> gather back

which keeps HLO memory at O(T·k·d + E·C·d) (no T×E×C dispatch tensors) and
makes the expert GEMM flops exactly 2·E·C·d·f — the quantity the roofline
needs.  Under GSPMD the (E, C, d) buffers shard over the model axis and the
scatter/gather lower to the all-to-all pattern of Fig 3.6 — on FengHuang
those are single shared-memory hops (tab schedule).

FengHuang fit (DESIGN.md §4): inactive experts never leave the remote tier;
with paging enabled the per-layer expert bank pages through local memory
while other layers compute — the paper's §2.1 motivation verbatim.

With ``PagerPolicy.page_experts`` the banks go one step further: they
stay at rest in the remote tier even while their layer computes, and
:func:`moe_ffn_topk` pages in only the rows the router selects (the
``TopKExpertPrefetch`` residency policy) — resident expert bytes drop to
``(tokens·top_k + 1) / num_experts`` of the dense bank, the
capacity-bound regime where disaggregated-memory designs pay off most.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.base import ModelConfig, dense_init
from repro.models.transformer import DenseLM


def capacity(tokens: int, num_experts: int, top_k: int, factor: float) -> int:
    c = int(math.ceil(tokens * top_k * factor / num_experts))
    return max(4, ((c + 3) // 4) * 4)


def moe_params(key, cfg: ModelConfig) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    e, d, f = cfg.padded_experts, cfg.d_model, cfg.d_ff
    return {
        "router": dense_init(k1, (d, e), jnp.float32),
        "wi": dense_init(k2, (e, d, f), cfg.dtype),
        "wg": dense_init(k3, (e, d, f), cfg.dtype),
        "wo": dense_init(k4, (e, f, d), cfg.dtype),
    }


def moe_specs() -> dict:
    return {
        "router": P(None, None, None),
        "wi": P(None, "model", None, None),
        "wg": P(None, "model", None, None),
        "wo": P(None, "model", None, None),
    }


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig,
            return_aux: bool = False):
    """x: (B, S, d) -> (B, S, d)[, aux_loss]."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.padded_experts, cfg.top_k
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    # mask padded experts
    col = jnp.arange(e)
    logits = jnp.where(col[None, :] < cfg.num_experts, logits, L.NEG_INF)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(gates, k)                   # (T, k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    cap = capacity(t, cfg.num_experts, k, cfg.capacity_factor)
    # position of each (token, choice) within its expert queue
    oh = jax.nn.one_hot(top_i, e, dtype=jnp.int32)           # (T, k, E)
    flat = oh.reshape(t * k, e)
    pos = (jnp.cumsum(flat, axis=0) - 1)                     # (T*k, E)
    pos_in_e = jnp.take_along_axis(
        pos.reshape(t, k, e), top_i[..., None], axis=-1)[..., 0]  # (T, k)
    keep = pos_in_e < cap
    safe_pos = jnp.where(keep, pos_in_e, cap - 1)

    # scatter tokens to (E, C, d) — expert parallelism: E over the model
    # axis.  Explicit constraints keep the dispatch/combine as
    # scatter/gather against E-sharded buffers with a single (T, d)
    # partial-sum reduction, instead of all-reducing the k-expanded
    # (T*k, d) tensor (§Perf iteration B: ~6x less MoE wire traffic).
    from repro.runtime.sharding import maybe_constraint
    from jax.sharding import PartitionSpec as P

    buf = jnp.zeros((e, cap, d), x.dtype)
    ei = top_i.reshape(-1)
    pi = safe_pos.reshape(-1)
    src = jnp.repeat(xt, k, axis=0) * keep.reshape(-1, 1).astype(x.dtype)
    buf = buf.at[ei, pi].add(src)
    buf = maybe_constraint(buf, P("model", None, None))

    # expert GEMMs (EP over model axis)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    out_e = jnp.einsum("ecf,efd->ecd", h, p["wo"])           # (E, C, d)
    out_e = maybe_constraint(out_e, P("model", None, None))

    # gather back and combine with gates
    gathered = out_e[ei, pi]                                 # (T*k, d)
    w = (top_g.reshape(-1) * keep.reshape(-1)).astype(x.dtype)
    combined = (gathered * w[:, None]).reshape(t, k, d).sum(axis=1)
    out = combined.reshape(b, s, d)
    from repro.models.base import BATCH_AXES
    out = maybe_constraint(out, P(BATCH_AXES, "model", None))

    if not return_aux:
        return out
    # GShard load-balance loss: E * sum_e f_e * P_e
    me = gates.mean(axis=0)                                  # (E,)
    ce = (jnp.sum(jax.nn.one_hot(top_i, e), axis=(0, 1)) /
          jnp.maximum(t * k, 1))
    aux = cfg.num_experts * jnp.sum(me * ce)
    return out, aux


# ---------------------------------------------------------------------------
# All-to-all expert parallelism (§Perf iteration D — the paper's Fig 3.6
# AllToAll pattern).  Tokens are seq-sharded; each device routes its local
# tokens, exchanges per-expert queues with the expert owners via two
# all-to-alls (wire ~= T*k*d per device instead of all-reducing E-sharded
# (E, C, d) buffers), runs its local experts' GEMMs, and combines locally.
# ---------------------------------------------------------------------------

def _moe_ep_available(cfg: ModelConfig, s: int) -> bool:
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return False
    sizes = dict(zip(am.axis_names, am.axis_sizes))
    tp = sizes.get("model", 1)
    return (tp > 1 and s % tp == 0 and cfg.padded_experts % tp == 0)


def moe_ffn_ep(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """shard_map EP MoE.  x: (B, S, d) with S divisible by the model axis."""
    from repro.models.base import BATCH_AXES
    am = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(am.axis_names, am.axis_sizes))
    tp = sizes["model"]
    e, k, d = cfg.padded_experts, cfg.top_k, cfg.d_model
    batch_axes = tuple(a for a in BATCH_AXES if a in sizes)

    def local(xs, router, wi, wg, wo):
        b_loc, s_loc, _ = xs.shape
        t = b_loc * s_loc
        xt = xs.reshape(t, d)
        logits = xt.astype(jnp.float32) @ router
        col = jnp.arange(e)
        logits = jnp.where(col[None, :] < cfg.num_experts, logits, L_NEG)
        gates = jax.nn.softmax(logits, axis=-1)
        top_g, top_i = jax.lax.top_k(gates, k)
        top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

        cap = capacity(t, cfg.num_experts, k, cfg.capacity_factor)
        oh = jax.nn.one_hot(top_i, e, dtype=jnp.int32)
        pos = jnp.cumsum(oh.reshape(t * k, e), axis=0) - 1
        pos_in_e = jnp.take_along_axis(
            pos.reshape(t, k, e), top_i[..., None], axis=-1)[..., 0]
        keep = pos_in_e < cap
        safe_pos = jnp.where(keep, pos_in_e, cap - 1)
        ei = top_i.reshape(-1)
        pi = safe_pos.reshape(-1)
        src = jnp.repeat(xt, k, axis=0) * keep.reshape(-1, 1).astype(xs.dtype)
        buf = jnp.zeros((e, cap, d), xs.dtype).at[ei, pi].add(src)

        # ship queues to the expert owners (TAB AllToAll on FengHuang)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)        # (E/tp, tp*cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wi)
        out_e = jnp.einsum("ecf,efd->ecd", h, wo)   # (E/tp, tp*cap, d)
        out_e = jax.lax.all_to_all(out_e, "model", split_axis=1,
                                   concat_axis=0, tiled=True)  # (E, cap, d)

        gathered = out_e[ei, pi]
        w = (top_g.reshape(-1) * keep.reshape(-1)).astype(xs.dtype)
        combined = (gathered * w[:, None]).reshape(t, k, d).sum(axis=1)
        return combined.reshape(b_loc, s_loc, d)

    from jax.sharding import PartitionSpec as P
    return jax.shard_map(
        local, mesh=am,
        in_specs=(P(batch_axes or None, "model", None),   # x seq-sharded
                  P(None, None),                          # router replicated
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(batch_axes or None, "model", None),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])


L_NEG = -1e30


# ---------------------------------------------------------------------------
# Expert-tier paging (repro.memory TopKExpertPrefetch policy): banks at
# rest in the remote tier, only routed rows paged in.
# ---------------------------------------------------------------------------

def moe_ffn_topk(p: dict, x: jax.Array, cfg: ModelConfig, mem) -> jax.Array:
    """MoE FFN that touches only the routed experts.

    x: (B, S, d).  Routing (logits -> softmax -> top-k -> capacity keep)
    is identical to :func:`moe_ffn`; the expert GEMMs are computed
    per-(token, choice) against ``tokens x k`` gathered bank rows
    (``mem.gather_experts`` — a page-in of just those rows when the
    banks live in the remote tier) instead of dense (E, C, d) buffers.
    Single-device path: expert parallelism keeps the EP all-to-all
    route; this one exists so expert weights can stay remote.
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.padded_experts, cfg.top_k
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    col = jnp.arange(e)
    logits = jnp.where(col[None, :] < cfg.num_experts, logits, L.NEG_INF)
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(gates, k)                   # (T, k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    # capacity keep — bit-compatible with the dense dispatch's dropping
    cap = capacity(t, cfg.num_experts, k, cfg.capacity_factor)
    oh = jax.nn.one_hot(top_i, e, dtype=jnp.int32)
    pos = jnp.cumsum(oh.reshape(t * k, e), axis=0) - 1
    pos_in_e = jnp.take_along_axis(
        pos.reshape(t, k, e), top_i[..., None], axis=-1)[..., 0]
    keep = pos_in_e < cap                                     # (T, k)

    ids = top_i.reshape(-1)                                   # (T*k,)
    rows = mem.gather_experts(p, ids)      # each (T*k, d, f) / (T*k, f, d)
    x_rep = jnp.repeat(xt, k, axis=0)                         # (T*k, d)
    h = jax.nn.silu(jnp.einsum("td,tdf->tf", x_rep, rows["wg"])) * \
        jnp.einsum("td,tdf->tf", x_rep, rows["wi"])
    out_tok = jnp.einsum("tf,tfd->td", h, rows["wo"])         # (T*k, d)
    w = (top_g.reshape(-1) * keep.reshape(-1)).astype(x.dtype)
    combined = (out_tok.astype(x.dtype) * w[:, None]).reshape(t, k, d) \
        .sum(axis=1)
    return combined.reshape(b, s, d)


class MoELM(DenseLM):
    """DenseLM with the FFN swapped for a top-k expert bank."""

    def init_layer(self, key) -> dict:
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "attn": L.attn_params(k1, cfg),
            "moe": moe_params(k2, cfg),
            "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
            "ln2": jnp.ones((cfg.d_model,), cfg.dtype),
        }

    def layer_specs(self) -> dict:
        return {
            "attn": L.attn_specs(self.cfg),
            "moe": moe_specs(),
            "ln1": P(None, None), "ln2": P(None, None),
        }

    def ffn(self, lp: dict, x: jax.Array, *,
            gather_tp: bool = False) -> jax.Array:
        # ``gather_tp`` is the dense-family all-gather-TP knob; the MoE
        # combine already sums expert outputs in replicated f32, so the
        # flag has nothing extra to gather here.
        # expert paging first: banks are at rest in the remote tier, so
        # the dense (E, C, d) dispatch would drag the whole bank through
        # local memory — gather only the routed rows instead.  (EP over a
        # live mesh supersedes it: sharded banks ARE distributed memory.)
        if self.mem.expert_policy is not None \
                and not _moe_ep_available(self.cfg, x.shape[1]):
            return moe_ffn_topk(lp["moe"], x, self.cfg, self.mem)
        if _moe_ep_available(self.cfg, x.shape[1]):
            return moe_ffn_ep(lp["moe"], x, self.cfg)
        return moe_ffn(lp["moe"], x, self.cfg)
