"""The one traffic generator: reads a mix's parameters (``traffic/<mix>.json``)
and turns them, with the run's seed, into the requests of a run.

Steadiness across seeds: the sizes and the inter-arrival gaps come in blocks
of ``block`` requests, and every block holds the same values, drawn once
from the mix's ``base_seed``.  The arrival times are the mix's own (the
gaps' order comes from ``base_seed`` too, so every seed meets the same
bursts); a run's seed draws the prompt tokens and the order of the output
lengths.  So every seed offers the same work at the same times.  A mix may
open with a ``backlog``: that many requests due at once when the generator
starts, as on a replica that is already busy.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float            # seconds after the generator's start
    prompt: np.ndarray      # (prompt_tokens,) int32
    max_new_tokens: int


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` output lengths: stratified quantiles of the distribution."""
    if isinstance(spec, int):
        return np.full(n, spec, np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist(math.log(spec["median"]), spec["sigma"])
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = np.clip(np.rint(np.exp(q)), spec["min"], spec["max"])
    return vals.astype(np.int64)


def _gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps whose mean is exactly 1 / rate."""
    rate = float(spec["rate_per_s"])
    if spec["process"] == "poisson":
        g = -np.log1p(-(np.arange(n) + 0.5) / n)
    elif spec["process"] == "gamma":
        shape = 1.0 / float(spec["cv"]) ** 2
        g = rng.gamma(shape, 1.0, n)
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return g / g.mean() / rate


def open_loop(mix: dict, seed: int, vocab: int, horizon_s: float,
              rate_per_s: float = None) -> List[Request]:
    """Requests due over ``horizon_s`` seconds (whole blocks)."""
    arrivals = dict(mix["arrivals"])
    if rate_per_s is not None:
        arrivals["rate_per_s"] = rate_per_s
    block = int(mix["block"])
    base = np.random.default_rng(int(mix["base_seed"]))
    lengths = _lengths(mix["output_tokens"], block, base)
    gaps = _gaps(arrivals, block, base)
    rng = np.random.default_rng(seed)
    backlog = int(mix.get("backlog", 0))
    n_blocks = max(1, math.ceil((horizon_s * float(arrivals["rate_per_s"])
                                 + backlog) / block))
    out, t = [], 0.0
    for _ in range(n_blocks):
        order = rng.permutation(block)
        g_order = base.permutation(block)
        for i in range(block):
            if len(out) >= backlog:
                t += float(gaps[g_order[i]])
            prompt = rng.integers(0, vocab, int(mix["prompt_tokens"]),
                                  dtype=np.int32)
            out.append(Request(t, prompt, int(lengths[order[i]])))
    return out
