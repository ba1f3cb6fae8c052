"""Serving traffic from a parameter file and a seed.

A traffic file (``bench/traffic/<name>.json``) states its kind and its
distributions; this one generator reads them all:

``{"kind": "backlog" | "open_loop", "prompt": {...}, "output": {...},
  "sizes": K, "rate_per_s": r (open_loop only), "requests": N (backlog),
  "order": "seeded" (the default) | "fixed"}``

``prompt`` and ``output`` are clipped log-normal length distributions:
``{"median": m, "sigma": s, "min": lo, "max": hi}``.

Every seed gets the same work, in another order. The schedule is made of
blocks; each block holds the same set of sizes and gaps, permuted by the
seed:

* a block's prompt lengths are picked evenly from the ``K`` quantiles
  ``(i + 0.5) / K`` of the prompt distribution (``K`` = ``sizes``), each
  about as often as the others; its output lengths are quantiles of
  their distribution over the block. A program compiles a few small
  pieces per distinct prompt length, so a set-up can warm these.
* a backlog's blocks hold ``sizes`` requests each, all due at once, up to
  ``requests`` or more.
* an open loop's block is the window: ``round(rate * seconds)`` requests
  whose gaps are the quantiles of an exponential distribution (a Poisson
  process's gaps), scaled to sum to ``seconds`` and permuted, so every
  seed has the same number of requests due in the window. Further blocks
  follow, so the load goes on for a minute past the close while the
  requests due in the window are followed to their first token. Arrival
  ``i`` is due at the sum of the first ``i`` gaps, in wall time from the
  start of the window, whatever the server is doing.
* ``order: fixed`` draws every block's permutations from one stream that
  no seed changes: every seed then sends the same sizes and gaps in the
  same order, and the seed changes only the token ids. For a mix whose
  tails depend on which prompts arrive while others decode, so that the
  order, not the server, would set the spread between seeds.
* token ids are uniform over ``[3, vocab)``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
from statistics import NormalDist

FIRST_TOKEN_ID = 3


@dataclass(frozen=True)
class Req:
    index: int
    due_s: float          # seconds after the window opens (0 for backlog)
    prompt: list
    max_new_tokens: int


def _lognormal_quantiles(spec: dict, count: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / count)
                  for i in range(count)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def prompt_sizes(traffic: dict) -> list[int]:
    """The distinct prompt lengths a traffic mix can send."""
    return sorted(set(_lognormal_quantiles(traffic["prompt"],
                                           traffic["sizes"]).tolist()))


def _block(traffic: dict, seconds: float) -> int:
    if traffic["kind"] == "backlog":
        return int(traffic["sizes"])
    if traffic["kind"] == "open_loop":
        return max(1, round(traffic["rate_per_s"] * seconds))
    raise ValueError(f"unknown traffic kind {traffic['kind']!r}")


def request_count(traffic: dict, seconds: float) -> int:
    """Requests in a schedule: whole blocks, for a backlog at least its
    stated count, for an open loop the window and a minute past it."""
    block = _block(traffic, seconds)
    if traffic["kind"] == "backlog":
        return block * -(-int(traffic["requests"]) // block)
    return block * (1 + math.ceil(60.0 / seconds))


def schedule(traffic: dict, seed: int, vocab: int, seconds: float) -> list:
    """The requests of one run, in arrival order."""
    block = _block(traffic, seconds)
    n = request_count(traffic, seconds)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, n])
    order = {"seeded": rng, "fixed": np.random.default_rng([0, 0, n])}[
        traffic.get("order", "seeded")]
    sizes = _lognormal_quantiles(traffic["prompt"], traffic["sizes"])
    # ``block`` picks spread evenly over the sizes, so a block smaller or
    # larger than ``sizes`` keeps the distribution's shape, tail included
    grid = sizes[((np.arange(block) + 0.5) * len(sizes) / block).astype(int)]
    outs = _lognormal_quantiles(traffic["output"], block)
    span = seconds if traffic["kind"] == "open_loop" else 0.0
    u = (np.arange(block) + 0.5) / block
    gaps = -np.log1p(-u)
    gaps *= span / gaps.sum()
    prompt_lens, out_lens, due = [], [], []
    for b in range(n // block):
        prompt_lens.extend(order.permutation(grid).tolist())
        out_lens.extend(order.permutation(outs).tolist())
        # an open loop's block b arrives in [b * seconds, (b + 1) * seconds)
        g = order.permutation(gaps)
        due.extend((b * span + np.concatenate([[0.0], np.cumsum(g)[:-1]]))
                   .tolist())
    return [
        Req(i, float(due[i]),
            rng.integers(FIRST_TOKEN_ID, vocab, size=int(prompt_lens[i]))
            .tolist(), int(out_lens[i]))
        for i in range(n)
    ]


def lateness(due_abs: list[float], sent_abs: list[float]) -> dict:
    """How late the generator sent requests: seconds after each one's due
    time (0 for one sent on time), summarised."""
    late = np.maximum(np.asarray(sent_abs) - np.asarray(due_abs), 0.0)
    if late.size == 0:
        return {"count": 0, "p50_s": 0.0, "max_s": 0.0}
    return {"count": int(late.size), "p50_s": float(np.median(late)),
            "max_s": float(late.max())}
