"""TAB shared-memory collectives (§3.3) mapped to JAX.

The FengHuang Tensor Addressable Bridge turns every collective into
shared-memory traffic: each xPU `write-accumulate`s its contribution into a
striped shared buffer (one transfer), the TAB notifies completion, and
consumers read.  TPU has no memory-side reduction, but the *schedule* —
"one write per device, accumulation at the owner, then direct reads" — is
exactly reduce-scatter(+all-gather) semantics.  We expose both:

* ``tab_*``  — one-shot implementations (`psum_scatter`/`all_gather`/
  `all_to_all`) matching FengHuang's single-transfer-per-device pattern.
* ``ring_*`` — explicit 2(N-1)-step `ppermute` rings modelling the paper's
  NVLink baseline.  These exist so benchmarks/tests can compare transfer
  *counts* (Enabler 1) on real HLO, and so the collective schedule is
  swappable per model config.

All functions are written against a named mesh axis and must run inside
``jax.shard_map``.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax

Schedule = Literal["tab", "ring"]


def _axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# One-shot "TAB" collectives.
# ---------------------------------------------------------------------------

def tab_write_accumulate(x: jax.Array, axis_name: str) -> jax.Array:
    """The TAB's in-memory accumulate: every device's contribution summed
    into the shared buffer.  Per-device traffic: one write of |x| (Enabler 1
    latency-bound count = 1) + one read of the result == psum."""
    return lax.psum(x, axis_name)


def tab_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """AllReduce (Fig 3.5): write-accumulate + completion + read-all."""
    return lax.psum(x, axis_name)


def tab_reduce_scatter(x: jax.Array, axis_name: str,
                       scatter_dimension: int = 0) -> jax.Array:
    """ReduceScatter (Fig 3.5): identical writes; each xPU reads its shard."""
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension, tiled=True)


def tab_allgather(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    """AllGather (Fig 3.6): each xPU writes its shard; all read the result."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def tab_all_to_all(x: jax.Array, axis_name: str, *, split_axis: int = 0,
                   concat_axis: int = 0) -> jax.Array:
    """AllToAll (Fig 3.6): shard writes + sliced reads."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def tab_p2p(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """P2P send/recv (Fig 3.7) as a single shared-memory hop."""
    n = _axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# Ring baselines ("NVLink" schedule): explicit 2(N-1) transfer steps.
# ---------------------------------------------------------------------------

def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """(N-1)-step ring reduce-scatter over leading-dim chunks.

    x: (d0, ...) with d0 divisible by N.  Returns this device's reduced
    chunk of shape (d0/N, ...).
    """
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    chunks = jnp.stack(jnp.split(x, n, axis=0))          # (N, d0/N, ...)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(k, acc_chunks):
        # At step k, device i sends its partial of chunk (i - k - 1) mod N
        # and accumulates the incoming partial into chunk (i - k - 2) mod N;
        # after N-1 steps device i owns the fully-reduced chunk i (matching
        # psum_scatter placement).
        send_idx = (idx - k - 1) % n
        send = jnp.take(acc_chunks, send_idx, axis=0)
        recv = lax.ppermute(send, axis_name, perm)
        tgt = (idx - k - 2) % n
        updated = jnp.take(acc_chunks, tgt, axis=0) + recv
        return acc_chunks.at[tgt].set(updated)

    chunks = lax.fori_loop(0, n - 1, step, chunks)
    return jnp.take(chunks, idx, axis=0)


def ring_allgather(x: jax.Array, axis_name: str) -> jax.Array:
    """(N-1)-step ring all-gather of per-device chunks along axis 0."""
    n = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    out = jnp.zeros((n,) + x.shape, x.dtype).at[idx].set(x)

    def step(k, state):
        buf, cur = state
        nxt = lax.ppermute(cur, axis_name, perm)
        src = (idx - k - 1) % n
        return buf.at[src].set(nxt), nxt

    out, _ = lax.fori_loop(0, n - 1, step, (out, x))
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Ring allreduce = ring reduce-scatter + ring all-gather: the paper's
    2(N-1)-transfer NVLink baseline (Enabler 1)."""
    n = _axis_size(axis_name)
    orig_shape = x.shape
    size = _size(orig_shape)
    flat = x.reshape(-1)
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = ring_reduce_scatter(flat, axis_name)
    full = ring_allgather(shard, axis_name)
    return full[:size].reshape(orig_shape)


def _size(shape) -> int:
    s = 1
    for d in shape:
        s *= int(d)
    return s


# ---------------------------------------------------------------------------
# Schedule dispatch used by model layers.
# ---------------------------------------------------------------------------

def allreduce(x: jax.Array, axis_name: str,
              schedule: Schedule = "tab") -> jax.Array:
    if schedule == "ring":
        return ring_allreduce(x, axis_name)
    return tab_allreduce(x, axis_name)


def reduce_scatter(x: jax.Array, axis_name: str,
                   schedule: Schedule = "tab") -> jax.Array:
    if schedule == "ring":
        return ring_reduce_scatter(x, axis_name)
    return tab_reduce_scatter(x, axis_name)


def allgather(x: jax.Array, axis_name: str,
              schedule: Schedule = "tab") -> jax.Array:
    if schedule == "ring":
        return ring_allgather(x, axis_name)
    return tab_allgather(x, axis_name)
