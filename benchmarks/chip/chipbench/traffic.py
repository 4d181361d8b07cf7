"""The one generator of requests.  A traffic mix is a data file of
parameters (``traffic/<name>.json``, with the cell's load from
``workloads/<cell>.json`` on top); this module turns it and a seed into
requests.  The program under test receives only what comes out here.

Requests come in blocks of ``block``.  Within a block the prompt
lengths, output lengths and (open loop) gaps between arrivals are one
stratified set, the distribution's quantiles at (i + 0.5) / block, in a
fixed order whose every prefix spreads over the distribution (see
:func:`spread_order`).  The seed draws the token ids only: every seed
offers the same work in the same order, so that runs differ by the
system's noise and not by the draw.

Distributions (``prompt_len``, ``output_len``):
    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
    {"dist": "uniform", "min": a, "max": b}          integers a..b
Arrivals (open loop): Poisson at ``rate_per_s``: exponential gaps,
scaled so that block ``k`` arrives over [k, k + 1) * block / rate; a
window of ``block / rate`` seconds that opens at a block boundary holds
that one block.

A closed loop starts at steady state: the first request of each of the
``clients`` clients is one caught in progress, as a client of a loop
that has run for long holds it.  Its output length is drawn in
proportion to the length (the request in progress at a given moment is
more likely a long one), the tokens it has already produced are a
stratified share of that length, and it arrives as a prompt of the
original prompt plus those tokens, asking for the rest.  The cache
then holds the contexts of a loop at steady state once the prompts are
in, and requests finish and new prompts arrive inside the window.
"""
from __future__ import annotations

import dataclasses
import itertools
import statistics
from typing import Dict, Iterator, List

import numpy as np


@dataclasses.dataclass
class Req:
    index: int                # order of generation
    prompt: List[int]
    max_new: int
    due_s: float              # arrival after the load starts (open loop)
    temperature: float


#: one irrational step per attribute, so that the orders are unrelated
STEPS = {"prompt": 0.6180339887498949, "output": 0.4142135623730951,
         "gap": 0.7320508075688772, "done": 0.1415926535897931}


def spread_order(n: int, what: str) -> np.ndarray:
    """A fixed permutation of ``range(n)`` whose every prefix spreads
    over the range: the ranks of frac(0.5 + k * step), k < n."""
    v = np.modf(0.5 + np.arange(n) * STEPS[what])[0]
    return np.argsort(np.argsort(v))


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """Integer quantiles of ``dist`` at (i + 0.5) / n, clipped."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        v = np.floor(lo + p * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in p])
        v = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def biased_quantiles(dist: Dict, n: int, fine: int = 4096) -> np.ndarray:
    """Quantiles at (i + 0.5) / n of ``dist`` weighted by the value: the
    length of a request caught in progress at a given moment."""
    v = quantiles(dist, fine).astype(np.float64)
    cdf = np.cumsum(v) / v.sum()
    p = (np.arange(n) + 0.5) / n
    return v[np.minimum(np.searchsorted(cdf, p), fine - 1)].astype(np.int64)


def exp_gaps(n: int, rate: float) -> np.ndarray:
    """Stratified exponential gaps that add up to ``n / rate``."""
    p = (np.arange(n) + 0.5) / n
    g = -np.log1p(-p)
    return g * (n / rate) / g.sum()


def block_seconds(mix: Dict) -> float:
    """How long one block of an open loop takes to arrive."""
    return int(mix["block"]) / float(mix["rate_per_s"])


def mix_params(traffic: Dict, workload: Dict) -> Dict:
    """The mix as a cell runs it: the traffic file, with the cell's
    workload file (its load) on top."""
    return {**traffic, **workload}


def in_progress(mix: Dict) -> List[tuple]:
    """(prompt length, tokens to produce) of each client's first request
    in a closed loop: the original prompt plus the share already produced
    of a length-biased output."""
    n = int(mix["clients"])
    p = quantiles(mix["prompt_len"], n)[spread_order(n, "prompt")]
    o = biased_quantiles(mix["output_len"], n)[spread_order(n, "output")]
    share = ((np.arange(n) + 0.5) / n)[spread_order(n, "done")]
    done = np.floor(share * o).astype(np.int64)
    return [(int(a + d), int(b - d)) for a, b, d in zip(p, o, done)]


def stream(mix: Dict, seed: int, vocab: int) -> Iterator[Req]:
    """Requests without end: in a closed loop the clients' first
    requests, caught in progress; then block after block."""
    rng = np.random.default_rng(int(seed))
    n = int(mix["block"])
    open_loop = mix["loop"] == "open"
    prompts = quantiles(mix["prompt_len"], n)[spread_order(n, "prompt")]
    outputs = quantiles(mix["output_len"], n)[spread_order(n, "output")]
    temp = float(mix.get("temperature", 0.0))
    index = 0
    if open_loop:
        gaps = exp_gaps(n, float(mix["rate_per_s"]))[spread_order(n, "gap")]
        offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        span = block_seconds(mix)
    else:
        offsets, span = np.zeros(n), 0.0
        for p, o in in_progress(mix):
            yield Req(index, rng.integers(0, vocab, p).tolist(), o, 0.0,
                      temp)
            index += 1
    for k in itertools.count():
        for i in range(n):
            toks = rng.integers(0, vocab, int(prompts[i])).tolist()
            yield Req(index, toks, int(outputs[i]),
                      float(k * span + offsets[i]), temp)
            index += 1


def take(mix: Dict, seed: int, vocab: int, count: int) -> List[Req]:
    it = stream(mix, seed, vocab)
    return [next(it) for _ in range(count)]
