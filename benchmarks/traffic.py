"""The one traffic generator. A traffic mix is a data file under
``benchmarks/traffic/``; this module turns it and a seed into requests
(serving) or batches (training).

Steadiness rule: a draw of ``n`` values from a distribution is the
distribution's ``n`` quantiles at ``(i + 0.5) / n``, in an order the seed
shuffles. So every seed offers the same set of lengths and the same set
of gaps between arrivals — the same work — in another order, with other
token ids.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, Iterator, List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def inverse_cdf(dist: Dict[str, float], u: np.ndarray) -> np.ndarray:
    """``dist`` at the probabilities ``u``, clipped to its range."""
    n = len(u)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        out = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "uniform":
        out = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "exponential":
        out = -np.log1p(-u) * dist["mean"]
    elif kind == "constant":
        out = np.full(n, float(dist["value"]))
    elif kind == "empirical":
        # a recorded histogram as data: its quantiles at evenly spaced
        # probabilities from 0 to 1, read by linear interpolation
        table = np.asarray(dist["quantiles"], float)
        out = np.interp(u, np.linspace(0.0, 1.0, len(table)), table)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist or "max" in dist:
        out = np.clip(out, dist.get("min", -np.inf), dist.get("max", np.inf))
    return out


def quantiles(dist: Dict[str, float], n: int) -> np.ndarray:
    """``n`` quantiles of ``dist`` (unshuffled), clipped to its range."""
    return inverse_cdf(dist, (np.arange(n) + 0.5) / n)


def draw(dist: Dict[str, float], n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(quantiles(dist, n))


def draw_lengths(dist, n, rng) -> np.ndarray:
    return np.rint(draw(dist, n, rng)).astype(np.int64)


def arrival_times(rate_per_s: float, span_s: float, rng) -> np.ndarray:
    """``round(rate × span)`` arrivals in ``[0, span)``: the exponential
    distribution's quantile gaps, shuffled, then scaled so that they fill
    the span exactly. The count and the set of gaps are the same under
    every seed."""
    n = int(round(rate_per_s * span_s))
    if n <= 0:
        return np.zeros(0)
    gaps = draw({"dist": "exponential", "mean": 1.0}, n, rng)
    t = np.cumsum(gaps) - gaps[0]
    return t * (span_s / float(np.sum(gaps)))


@dataclasses.dataclass
class ServeRequest:
    index: int
    due_s: float  # relative to the window's opening; negative in the lead
    prompt: np.ndarray
    max_new_tokens: int


def _requests(mix, n, rng, vocab, first_index, due) -> List[ServeRequest]:
    plens = draw_lengths(mix["prompt_len"], n, rng)
    olens = draw_lengths(mix["output_len"], n, rng)
    return [
        ServeRequest(
            first_index + i, float(due[i]),
            rng.integers(0, vocab, size=int(plens[i]), dtype=np.int64).astype(np.int32),
            int(olens[i]),
        )
        for i in range(n)
    ]


def open_loop_requests(mix: dict, seed: int, seconds: float, vocab: int,
                       tail_s: float) -> List[ServeRequest]:
    """Lead, window and tail, each drawn as its own set: the lead fills
    the server before the window opens, the tail keeps the load on while
    the window's last requests finish. Requests of the window are those
    with ``0 <= due_s < seconds``."""
    rng = np.random.default_rng([int(seed), 1])
    rate = float(mix["rate_per_s"])
    out: List[ServeRequest] = []
    for start, span in ((-float(mix["lead_s"]), float(mix["lead_s"])),
                        (0.0, float(seconds)), (float(seconds), tail_s)):
        t = arrival_times(rate, span, rng) + start
        out += _requests(mix, len(t), rng, vocab, len(out), t)
    return out


def closed_loop_requests(mix: dict, seed: int, vocab: int) -> Iterator[ServeRequest]:
    """An endless backlog: blocks of ``clients`` requests, each block the
    same set of lengths in another order."""
    rng = np.random.default_rng([int(seed), 2])
    n, index = int(mix["clients"]), 0
    while True:
        for r in _requests(mix, n, rng, vocab, index, np.zeros(n)):
            yield r
        index += n


def token_batches(seed: int, rows: int, seq_len: int, vocab: int
                  ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Training batches of next-token pairs: every row drawn afresh."""
    rng = np.random.default_rng([int(seed), 3])
    while True:
        x = rng.integers(0, vocab, size=(rows, seq_len + 1), dtype=np.int64).astype(np.int32)
        yield x[:, :-1], x[:, 1:]
