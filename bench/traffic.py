"""The one traffic generator: reads a mix's parameters from its data file.

A mix lives in ``bench/traffic/<name>.json``.  Two kinds exist:

* ``open_loop`` -- requests sent on a schedule whatever the server does.
  Sizes and gaps are stratified draws: the i-th of N values is the
  distribution's quantile at (i + 0.5) / N.  One arrangement of them,
  drawn from the mix's ``order_seed``, serves every seed, and the seed picks
  only the token ids.  So runs on different seeds offer the same work at
  the same times and differ in content alone.
* ``closed_loop`` -- back-to-back steps over one fixed input shape.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


@dataclass(frozen=True)
class Request:
    due_s: float  # offset from the window's start
    prompt: np.ndarray  # int32 token ids
    max_new: int


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws of a length distribution, clipped and rounded."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(spec: dict, n: int, rate: float) -> np.ndarray:
    """n stratified inter-arrival gaps whose sum is exactly n / rate, so that
    n = rate x span requests fit a span."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (n / rate) / g.sum()


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              rate: float | None = None) -> list[Request]:
    """The ``rate x seconds`` requests due in a window, in order of due time
    (seconds from the window's start)."""
    rate = mix["arrivals"]["rate_per_s"] if rate is None else rate
    n = max(int(math.floor(rate * seconds)), 1)
    prompt_len = quantiles(mix["prompt_tokens"], n)
    max_new = quantiles(mix["output_tokens"], n)
    g = gaps(mix["arrivals"], n, rate)
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix["order_seed"])  # one arrangement for every seed
    prompt_len, max_new, g = (order.permutation(prompt_len), order.permutation(max_new),
                              order.permutation(g))
    due = np.cumsum(g) - g / 2  # each request in the middle of its gap
    return [
        Request(due_s=float(due[i]),
                prompt=rng.integers(0, vocab, int(prompt_len[i]), dtype=np.int32),
                max_new=int(max_new[i]))
        for i in range(n)
    ]
