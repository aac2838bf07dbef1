"""The one traffic generator: it reads a mix's parameters from
`bench/traffic/<mix>.json` and draws its inputs from the seed.

Every seed gets the same multiset of sizes and of gaps between
arrivals, drawn at stratified quantiles of the mix's distributions, in
an order that the seed shuffles.  So two seeds do the same work in a
different order, and the spread between seeds is the system's, not the
draw's.  Token ids are uniform over the vocabulary; each request's ids
and its sampling seed come from a stream of their own, (seed, request
index).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    prompt: list
    max_new: int
    greedy: bool
    due: float = 0.0          # seconds after the start of arrivals
    sample_seed: int = 0      # the request's sampling seed


def _round_up(x: float, multiple: int) -> int:
    return int(math.ceil(x / multiple) * multiple)


def quantile_sizes(dist: dict, n: int) -> list:
    """n sizes at the quantiles (i + 0.5) / n of the distribution."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    mult = dist.get("multiple", 1)
    if kind == "grid":
        grid = list(range(dist["min"], dist["max"] + 1, mult))
        return [grid[i % len(grid)] for i in range(n)]
    if kind == "lognormal":
        z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
        raw = [dist["median"] * math.exp(dist["sigma"] * x) for x in z]
        return [min(max(_round_up(r, mult), dist["min"]), dist["max"])
                for r in raw]
    raise ValueError(f"unknown size distribution {kind!r}")


def sizes(dist: dict) -> set:
    """Every size the distribution can give."""
    kind = dist["dist"]
    if kind == "fixed":
        return {int(dist["value"])}
    mult = dist.get("multiple", 1)
    return set(range(_round_up(dist["min"], mult), dist["max"] + 1, mult))


def windows(prompt_len: int, chunk: int) -> list:
    """The engine's prefill windows of a prompt: full chunks, then the
    rest (a prompt no longer than a chunk is one window)."""
    if prompt_len <= chunk:
        return [prompt_len]
    full, rest = divmod(prompt_len, chunk)
    return [chunk] * full + ([rest] if rest else [])


def window_shapes(traffic: dict) -> tuple:
    """(lengths of windows before the last, lengths of last windows)
    over every prompt the mix can draw: the programs set-up compiles."""
    chunk = traffic["engine"]["prefill_chunk"]
    inner, last = set(), set()
    for n in sizes(traffic["prompt"]):
        w = windows(n, chunk)
        inner.update(w[:-1])
        last.add(w[-1])
    return sorted(inner), sorted(last)


def warmup_prompts(traffic: dict) -> list:
    """Prompt lengths that run every window program of the mix once."""
    chunk = traffic["engine"]["prefill_chunk"]
    inner, last = window_shapes(traffic)
    lengths = [(chunk if inner else 0) + n for n in last]
    return lengths


def _tokens(seed: int, index: int, n: int, vocab: int) -> tuple:
    """The request's prompt ids and its sampling seed."""
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), index])
    return rng.integers(0, vocab, size=n).tolist(), int(
        rng.integers(0, 2 ** 31 - 1))


def _greedy(traffic: dict, index: int) -> bool:
    s = traffic["sampling"]
    if s["temperature"] <= 0:
        return True
    every = s.get("greedy_every", 0)
    return bool(every) and index % every == 0


def open_loop(traffic: dict, vocab: int, seed: int, horizon_s: float) -> list:
    """Requests due over [0, horizon_s): Poisson arrivals at the mix's
    rate, as a fixed multiset of exponential gaps in a seeded order."""
    rate = traffic["rate_per_s"]
    n = int(math.ceil(rate * horizon_s))
    rng = np.random.default_rng(int(seed) & (2 ** 64 - 1))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    prompts = quantile_sizes(traffic["prompt"], n)
    outputs = quantile_sizes(traffic["output"], n)
    gaps = [gaps[i] for i in rng.permutation(n)]
    prompts = [prompts[i] for i in rng.permutation(n)]
    outputs = [outputs[i] for i in rng.permutation(n)]
    out, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        ids, sample_seed = _tokens(seed, i, prompts[i], vocab)
        out.append(Request(i, ids, outputs[i], _greedy(traffic, i), due=t,
                           sample_seed=sample_seed))
    return out


class ClosedLoop:
    """Requests for a fixed pool of clients: the next one is asked for
    when a client's last request finishes.  Sizes cycle through the
    mix's multiset, each cycle in an order of its own."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.traffic, self.vocab, self.seed = traffic, vocab, seed
        self.rng = np.random.default_rng(int(seed) & (2 ** 64 - 1))
        self.cycle = sorted(sizes(traffic["prompt"]))
        self.order: list = []
        self.count = 0

    def next(self) -> Request:
        if not self.order:
            self.order = [self.cycle[i]
                          for i in self.rng.permutation(len(self.cycle))]
        n = self.order.pop()
        i = self.count
        self.count += 1
        max_new = quantile_sizes(self.traffic["output"], 1)[0]
        ids, sample_seed = _tokens(self.seed, i, n, self.vocab)
        return Request(i, ids, max_new, _greedy(self.traffic, i),
                       sample_seed=sample_seed)


def train_batch(traffic: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """The batch of step `step`: (batch, seq_len) int32, uniform ids; a
    stream of its own per step, so every row of every step differs."""
    rng = np.random.default_rng([int(seed) & (2 ** 64 - 1), 1 << 40, step])
    return rng.integers(0, vocab, size=(traffic["batch"], traffic["seq_len"]),
                        dtype=np.int32)
