"""The one traffic generator: requests, sources and arrival times from a
mix's parameters and the run's seed.

A mix file (``traffic/<name>.json``) sets:

- ``loop``: ``"closed"`` (``outstanding`` requests kept in flight),
  ``"open"`` (``rate_per_s`` arrivals, from ``arrivals``) or ``"batch"``;
- ``max_requests`` (closed loop): the block size of its endless supply
  (:meth:`Mix.stream`). Block 0 is ``requests(max_requests)``; each later
  block is drawn when the one before is spent, from a spawn key of its
  own, so a loop never runs dry whatever rate the system reaches, and
  every rate sends the same requests as far as it gets;
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
from typing import Iterator, List, Optional

import numpy as np

# Independent streams of one seed (numpy SeedSequence spawn keys). Block i
# of a closed loop's supply draws from STREAM_SOURCES + BLOCK_STRIDE * i.
STREAM_SOURCES, STREAM_ARRIVALS, STREAM_WARMUP = 1, 2, 4
BLOCK_STRIDE = 100


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
        return [self._request(s) for s in src]

    def stream(self) -> Iterator[Request]:
        """A closed loop's endless supply, in blocks of ``max_requests``:
        block 0 is ``requests(max_requests)``, block i draws from stream
        ``STREAM_SOURCES + BLOCK_STRIDE * i``. ``"uniform"`` sources never
        repeat within the supply (later blocks leave out every source
        already sent) and run out only after all ``n``, with an error;
        ``"zipf"`` keeps block 0's permutation, so the same vertices stay
        popular."""
        block = int(self.params["max_requests"])
        first = self.requests(block)
        yield from first
        uniform = self.params["sources"]["dist"] == "uniform"
        if uniform:
            free = np.ones(self.n, bool)
            free[[r.source for r in first]] = False
        else:
            perm = rng(self.seed, self._off + STREAM_SOURCES).permutation(
                self.n)
        i = 1
        while True:
            r = rng(self.seed, self._off + STREAM_SOURCES + BLOCK_STRIDE * i)
            if uniform:
                pool = np.flatnonzero(free)
                if not len(pool):
                    raise RuntimeError(
                        f"mix {self.params.get('name', '?')!r}, seed "
                        f"{self.seed}: all {self.n} sources sent after "
                        f"{self.n} requests, and uniform sources never repeat")
                src = r.choice(pool, size=min(block, len(pool)),
                               replace=False)
                free[src] = False
            else:
                src = self.sources(block, r, perm)
            for s in src:
                yield self._request(s)
            i += 1

    def _request(self, source) -> Request:
        rq = self.params["request"]
        return Request(k=int(rq["k"]), epsilon=float(rq["epsilon"]),
                       delta=float(rq["delta"]), source=int(source))

    def sources(self, count: int, r: np.random.Generator,
                perm: Optional[np.ndarray] = None) -> np.ndarray:
        """``count`` sources drawn with ``r``; ``perm``, for ``"zipf"``, the
        popularity order (else drawn from ``r`` first)."""
        spec = self.params["sources"]
        if spec["dist"] == "uniform":
            if count > self.n:
                raise ValueError(f"{count} distinct sources from "
                                 f"{self.n} vertices")
            return r.choice(self.n, size=count, replace=False)
        if spec["dist"] == "zipf":
            if perm is None:
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
