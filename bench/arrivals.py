"""The one traffic generator: requests, sources and arrival times from a
mix's parameters and the run's seed.

A mix file (``traffic/<name>.json``) sets:

- ``loop``: ``"closed"`` (``outstanding`` requests kept in flight),
  ``"open"`` (``rate_per_s`` arrivals, from ``arrivals``) or ``"batch"``;
- ``request``: every request's ``{"k", "epsilon", "delta"}``, all PPR;
- ``sources``: ``{"dist": "uniform"}`` (never repeated) or
  ``{"dist": "zipf", "constant": 0.99}`` (YCSB's zipfian over a permutation
  of the vertices drawn from the seed);
- ``arrivals`` (open loop): ``{"process": "poisson", "rate_per_s"}``;
- ``warmup_s``: how long the cell's own traffic runs before the window.

Every seed gets the same number of arrivals in a window (the Poisson
process conditioned on its count: uniform arrival times), so seeds change
the order of the work and not its amount.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

# Independent streams of one seed (numpy SeedSequence spawn keys).
STREAM_SOURCES, STREAM_ARRIVALS, STREAM_WARMUP = 1, 2, 4


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass(frozen=True)
class Request:
    """One PPR request."""
    k: int
    epsilon: float
    delta: float
    source: int


def zipf_ranks(r: np.random.Generator, n: int, count: int,
               constant: float) -> np.ndarray:
    """YCSB's ZipfianGenerator (Gray et al., "Quickly generating
    billion-record synthetic databases"): ranks in [0, n), rank 0 most
    popular, P(rank i) proportional to 1/(i+1)^constant."""
    theta = constant
    zetan = float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -theta))
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = r.random(count)
    uz = u * zetan
    ranks = (n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    ranks = np.where(uz < 1.0, 0, ranks)
    return np.clip(ranks, 0, n - 1)


class Mix:
    """One traffic mix over a graph of ``n`` vertices, for one seed."""

    def __init__(self, params: dict, n: int, seed: int, stream_offset=0):
        self.params = params
        self.n = n
        self.loop = params["loop"]
        self.seed = seed
        self._off = stream_offset

    def requests(self, count: int, warmup: bool = False) -> List[Request]:
        """The first ``count`` requests of the stream (``warmup``: a stream
        of its own, so warm-up traffic never shares the window's sources)."""
        off = self._off + (10 * STREAM_WARMUP if warmup else 0)
        src = self.sources(count, rng(self.seed, off + STREAM_SOURCES))
        rq = self.params["request"]
        return [Request(k=int(rq["k"]), epsilon=float(rq["epsilon"]),
                        delta=float(rq["delta"]), source=int(s))
                for s in src]

    def sources(self, count: int, r: np.random.Generator) -> np.ndarray:
        spec = self.params["sources"]
        if spec["dist"] == "uniform":
            if count > self.n:
                raise ValueError(f"{count} distinct sources from "
                                 f"{self.n} vertices")
            return r.choice(self.n, size=count, replace=False)
        if spec["dist"] == "zipf":
            perm = r.permutation(self.n)
            return perm[zipf_ranks(r, self.n, count, spec["constant"])]
        raise ValueError(f"unknown source distribution {spec['dist']!r}")

    def arrival_times(self, duration_s: float, warmup: bool = False
                      ) -> np.ndarray:
        """Due times in [0, duration_s), sorted: ``rate_per_s ·
        duration_s`` Poisson arrivals (rounded), uniform over the window."""
        arr = self.params["arrivals"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        off = self._off + (10 * STREAM_WARMUP if warmup else 0)
        r = rng(self.seed, off + STREAM_ARRIVALS)
        count = int(round(float(arr["rate_per_s"]) * duration_s))
        return np.sort(r.random(count) * duration_s)
