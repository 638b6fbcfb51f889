"""The one traffic generator: a traffic file's parameters and a seed in,
requests out.

Every seed gets the same work: lengths are the distribution's quantiles
at (i + 1/2)/n and open-loop gaps the exponential distribution's,
scaled so that the schedule spans its warm-up and window exactly, all
in one fixed shuffled order; the seed draws the prompt tokens (and the
weights, elsewhere).  Drawing the order from the seed too made the
chat cell's 90th-percentile wait differ 4.6-fold between seeds against
1.3-fold between two runs of one seed (section 6 of PERF.md): which
long prompts arrive together decides the queue, so the order is part of
the work, and it is fixed.

Traffic file keys:

- ``loop``: ``"open"`` (``rate_per_s``, ``warmup_s``: arrivals on a
  schedule) or ``"closed"`` (``clients``, each sending its next request
  when its last completes; the window opens once ``clients`` requests
  have their first token);
- ``prompt`` and ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``; a prompt may
  set ``"round_up_pow2": true``, which rounds each length up to the next
  power of two: the lengths at which the program's admission adds no
  padding (it pads a prompt with token 0 to a power of two and attends
  the padding, so only these lengths give the model's own answers).

A key the generator does not read is refused.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

CLOSED_POOL_PER_CLIENT = 64     # requests drawn per closed-loop client
ORDER = 20261018                # the one shuffle of lengths and gaps


@dataclasses.dataclass
class Spec:
    """One request as the generator draws it."""
    index: int
    prompt_len: int
    output_len: int
    due_s: float | None = None       # open loop: offset from stream start


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified lengths of ``dist``, in ascending order."""
    u = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        x = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = np.clip(x, lo, hi).astype(np.int64)
    if dist.get("round_up_pow2"):
        x = np.array([1 << max(int(v) - 1, 0).bit_length() for v in x])
    return x


def _draw(traffic: dict, n: int, rng: np.random.Generator) -> list[Spec]:
    prompts = rng.permutation(lengths(traffic["prompt"], n))
    outputs = rng.permutation(lengths(traffic["output"], n))
    return [Spec(i, int(p), int(o)) for i, (p, o) in
            enumerate(zip(prompts, outputs))]


def open_schedule(traffic: dict, seconds: float) -> list[Spec]:
    """Requests due over ``warmup_s + seconds``, in due order."""
    total = traffic["warmup_s"] + seconds
    n = max(1, round(traffic["rate_per_s"] * total))
    rng = np.random.default_rng([ORDER, 1])
    specs = _draw(traffic, n, rng)
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    for s, t in zip(specs, starts * total / gaps.sum()):
        s.due_s = float(t)
    return specs


def closed_pool(traffic: dict) -> list[Spec]:
    """The requests closed-loop clients take, in the order they take
    them."""
    n = traffic["clients"] * CLOSED_POOL_PER_CLIENT
    return _draw(traffic, n, np.random.default_rng([ORDER, 2]))


TRAFFIC_KEYS = {"name", "loop", "rate_per_s", "warmup_s", "clients",
                "prompt", "output"}
LENGTH_KEYS = {"dist", "median", "sigma", "min", "max", "round_up_pow2"}


def specs(traffic: dict, seconds: float) -> list[Spec]:
    unknown = (set(traffic) - TRAFFIC_KEYS) | (
        set(traffic["prompt"]) | set(traffic["output"])) - LENGTH_KEYS
    if unknown:
        raise ValueError(f"traffic {traffic.get('name')!r}: keys the "
                         f"generator does not read: {sorted(unknown)}")
    if traffic["loop"] == "open":
        return open_schedule(traffic, seconds)
    if traffic["loop"] == "closed":
        return closed_pool(traffic)
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def prompt_tokens(spec: Spec, vocab: int, seed: int) -> np.ndarray:
    """The prompt of one request: token ids uniform over [1, vocab)."""
    rng = np.random.default_rng([seed, 3, spec.index])
    return rng.integers(1, vocab, size=spec.prompt_len, dtype=np.int32)


def max_lengths(traffic: dict) -> tuple[int, int]:
    """The longest prompt and output the traffic can draw."""
    p, o = traffic["prompt"], traffic["output"]
    pmax = int(p["max"])
    if p.get("round_up_pow2"):
        pmax = 1 << max(pmax - 1, 0).bit_length()
    return pmax, int(o["max"])
