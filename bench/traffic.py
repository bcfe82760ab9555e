"""The one traffic generator: a mix file's parameters and a seed → requests.

Sizes are drawn so that every seed gets the same work in another order. The
requests come in blocks of ``block``; each block holds the ``block``
stratified quantiles, at (i + 0.5) / block, of each length distribution,
shuffled by the seed. A window that serves some blocks therefore sees the
same prompt and output lengths whatever the seed; the seed picks their order
and the prompt tokens. A mix sets ``block`` to what its window serves at a
time (a pool's slots), so that the cut at the window's close falls inside
one block.

Copied in idea from the program's ``serve/traffic.poisson_trace`` (prompt
lengths from a distribution); its uniform bands of 4-120 tokens are replaced
by clipped lognormal lengths.

The one arrival pattern so far is ``backlog``: ``requests`` requests, all due
when the window opens.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Req:
    """A generated request; drivers turn it into the program's ``Request``."""

    id: str
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int
    arrival_s: float


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams of one seed (any non-negative integer)."""
    return np.random.default_rng([int(seed), int(stream)])


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse of the standard normal CDF (Acklam's rational approximation,
    relative error below 1.2e-9), so no scipy is needed."""
    a = [-3.969683028665376e01, 2.209460984245205e02, -2.759285104469687e02,
         1.383577518672690e02, -3.066479806614716e01, 2.506628277459239e00]
    b = [-5.447609879822406e01, 1.615858368580409e02, -1.556989798598866e02,
         6.680131188771972e01, -1.328068155288572e01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e00,
         -2.549732539343734e00, 4.374664141464968e00, 2.938163982698783e00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e00,
         3.754408661907416e00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]) * q / \
        (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1)
    return out


def stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_quantiles(dist: dict, block: int) -> np.ndarray:
    """The ``block`` stratified quantiles of a clipped lognormal, as ints."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = _norm_ppf(stratified(block))
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def request_count(traffic: dict) -> int:
    arr = traffic["arrivals"]
    if arr["pattern"] != "backlog":
        raise ValueError(f"unknown arrival pattern {arr['pattern']!r}")
    return int(arr["requests"])


def generate(traffic: dict, seed: int, vocab: int) -> list[Req]:
    """The mix's requests for one run, sorted by arrival."""
    n = request_count(traffic)
    block = int(traffic["block"])
    order = rng_for(seed, 0)
    plens = length_quantiles(traffic["prompt"], block)
    olens = length_quantiles(traffic["output"], block)
    n_blocks = -(-n // block)
    P = np.concatenate([order.permutation(plens) for _ in range(n_blocks)])[:n]
    O = np.concatenate([order.permutation(olens) for _ in range(n_blocks)])[:n]
    toks = rng_for(seed, 1)
    return [
        Req(id=f"r{i:05d}",
            prompt=toks.integers(1, vocab, size=int(P[i])).astype(np.int32),
            max_new_tokens=int(O[i]),
            arrival_s=0.0)
        for i in range(n)
    ]
