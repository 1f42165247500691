"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) states its loop, its rate or number
of clients, its length distributions, its workload class and its engine
buckets. ``shape_seed`` fixes the sizes and the arrival gaps: every run seed
gets the same sizes and gaps, permuted within blocks of ``block`` requests,
so a window holds nearly the same work in another order. The run seed draws the
order and the token ids.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Req:
    uid: int
    prompt: list
    max_new: int
    due: Optional[float] = None       # seconds after the window opens
    client: Optional[int] = None


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) % (1 << 63) for w in words])


def _lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _block(mix: dict, b: int, seed: int):
    """Sizes and gaps of block ``b``: drawn from the shape seed, then put
    in the order the run seed gives."""
    n = mix["block"]
    base = _rng(mix["shape_seed"], b)
    prompt = _lengths(mix["prompt"], base, n)
    out = _lengths(mix["output"], base, n)
    gaps = (base.exponential(1.0 / mix["rate_per_s"], n)
            if mix["loop"] == "open" else np.zeros(n))
    order = _rng(seed, b, 1)
    p = order.permutation(n)
    return prompt[p], out[p], gaps[order.permutation(n)]


def stream(mix: dict, seed: int, vocab: int, n: int) -> list:
    """The first ``n`` requests of the mix for this run seed. Open loop:
    ``due`` is the arrival time. Closed loop: request ``i`` belongs to
    client ``i % clients``, which sends it when its previous one returns."""
    blocks = [_block(mix, b, seed)
              for b in range(-(-n // mix["block"]))]
    prompt = np.concatenate([b[0] for b in blocks])[:n]
    out = np.concatenate([b[1] for b in blocks])[:n]
    due = np.cumsum(np.concatenate([b[2] for b in blocks]))[:n]
    tok = _rng(seed, 1 << 20)
    reqs = []
    for i in range(n):
        r = Req(uid=i, prompt=tok.integers(0, vocab, int(prompt[i])).tolist(),
                max_new=int(out[i]))
        if mix["loop"] == "open":
            r.due = float(due[i])
        else:
            r.client = i % mix["clients"]
        reqs.append(r)
    return reqs


def count_for(mix: dict, seconds: float) -> int:
    """Enough requests for a window of ``seconds``: three times the
    expected arrivals of an open loop; 256 for each client of a closed
    one."""
    if mix["loop"] == "open":
        return int(3 * mix["rate_per_s"] * seconds) + 2 * mix["block"]
    return mix["clients"] * 256


def warmup(mix: dict, n_slots: int) -> list:
    """The same few small requests for every seed: they fill every slot,
    free one and admit into it, so each host-side program the window uses
    is compiled before it opens."""
    return [Req(uid=-1 - i, prompt=[1 + i] * (4 + i), max_new=2 + i % 3)
            for i in range(n_slots + 2)]
