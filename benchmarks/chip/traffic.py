"""The one traffic generator: reads a mix's parameters and makes requests.

A mix file (``traffic/<mix>.json``) holds:

- ``loop``: ``"open"`` (requests fall due on a schedule, whatever the
  server does) or ``"closed"`` (``clients`` callers, each sending its next
  request when the last one finished);
- ``rate_rps`` (open) or ``clients`` (closed);
- ``prompt`` and ``output``: length distributions, ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
  "max"}``, in tokens;
- ``lead_in_s``: seconds of the same traffic before the window, so that
  the slots are in steady state when it opens;
- ``shape_seed``: fixes how prompt and output lengths pair up;
- ``block`` (closed): requests per stratified block.

Every seed gets the same lengths and the same arrival gaps in the same
order; the seed draws the prompts' tokens (and ``run.py`` the weights).
Lengths are the distribution's quantiles at (i + 1/2) / n, paired and
ordered by ``shape_seed``, and open-loop gaps are the exponential's
quantiles scaled to fill their span exactly (a stratified Poisson
process).  A dense model decoding greedily to ``max_new`` takes the same
time whatever its tokens, so every seed offers the same work on the same
schedule, and the spread between runs is the system's own.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the client plans it: due ``due`` seconds after the
    window opens (negative in the lead-in; None in a closed loop, where
    it falls due when its client's previous request finished)."""

    prompt: np.ndarray
    max_new: int
    due: float = None


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    q = _quantiles(n)
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        v = dist["min"] + np.floor(q * (dist["max"] - dist["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def pairs(mix: dict, n: int) -> np.ndarray:
    """(n, 2) prompt and output lengths, the same for every seed."""
    p = lengths(mix["prompt"], n)
    o = lengths(mix["output"], n)
    o = o[np.random.default_rng(mix["shape_seed"]).permutation(n)]
    return np.stack([p, o], axis=1)


def _gaps(n: int, span: float, rng) -> np.ndarray:
    g = -np.log1p(-_quantiles(n))
    g = g * (span / g.sum())
    return g[rng.permutation(n)]


def _request(rng, plen: int, olen: int, vocab: int) -> Planned:
    toks = rng.integers(1, vocab, int(plen)).astype(np.int32)
    return Planned(toks, int(olen))


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    """The lead-in's and the window's requests, in due order."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix["shape_seed"])
    out = []
    for start, span in ((-mix["lead_in_s"], mix["lead_in_s"]),
                        (0.0, float(seconds))):
        n = max(1, round(mix["rate_rps"] * span))
        lp = pairs(mix, n)[order.permutation(n)]
        due = start + np.concatenate([[0.0], np.cumsum(_gaps(n, span, order))[:-1]])
        for (plen, olen), t in zip(lp, due):
            r = _request(rng, plen, olen, vocab)
            r.due = float(t)
            out.append(r)
    return out


def closed_loop(mix: dict, seed: int, vocab: int):
    """An endless stream of requests for the clients to take in turn."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix["shape_seed"])
    block = pairs(mix, mix["block"])
    while True:
        for plen, olen in block[order.permutation(len(block))]:
            yield _request(rng, plen, olen, vocab)


def bucket(n: int) -> int:
    """The power-of-two prompt bucket (8 and up) a prompt of ``n`` tokens
    pads to."""
    return max(8, 1 << (n - 1).bit_length())


def buckets(mix: dict, max_seq: int) -> List[int]:
    """The prompt buckets this mix's prompts pad to.  A bucket whose rows
    and longest output overrun ``max_seq`` would make the program fall
    back to an exact-length prefill, which compiles in the window, so such
    a mix is refused."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out = sorted({bucket(n) for n in range(lo, hi + 1)})
    for b in out:
        if b + mix["output"]["max"] > max_seq:
            raise ValueError(f"bucket {b} + {mix['output']['max']} new tokens "
                             f"overruns max_seq {max_seq}")
    return out
