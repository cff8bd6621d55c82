"""The comparison that decides ``correct``: served answers against the
plain references, each number beside its limit.

Serving cells (every answer due in the window, and a sample of them drawn
from the seed for the statistical numbers):

- ``unanswered``: admitted requests that never settled, a minute past the
  window's close at the latest (limit 0);
- ``malformed``: answers that break what the request and its plan state:
  ``k`` distinct vertices of the graph, the plan's walk count ``N``, its
  truncation ``t`` and its Theorem-1 ``epsilon_bound``, not degraded
  (limit 0);
- ``ppr_mass_deficit``: the mean over the sampled answers of
  ``1 - p(V) / mu_k(p)``, the share of the best k vertices' PPR mass that
  the served k vertices miss, with ``p`` the reference's estimate of the
  source's PPR. It is Theorem 1's quantity, normalized, and steady from
  seed to seed; an index cut to a quarter reads about nine times what the
  configured one does;
- ``ppr_mass_shortfall``: the largest ``mu_k(p) - p(V)`` over the sample,
  against the answers' own ``epsilon_bound`` (Theorem 1 as stated).

Batch cells (a sample of the window's estimates):

- ``frogs_lost``: frogs launched but not tallied, and estimates whose
  ``pi_hat`` is not ``counts / N`` (limit 0);
- ``frog_count_z2``: mean ``z^2`` of the counts at the reference's top
  vertices of the truncated walk's exact distribution (no selection by the
  estimate, so a sound run reads 1);
- ``pagerank_mass_shortfall``: ``mu_k(pi) - pi(top-k of pi_hat)`` against
  global PageRank, with Theorem 1's ``epsilon_bound`` at ``(N, t)``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from bench.reference import walks as ref_walks

SAMPLE_ANSWERS = 64          # PPR answers compared with the reference
REF_WALKS_PER_WALK = 32      # reference walks per walk the answer used
BATCH_TOP = 1000             # reference top vertices whose counts are compared


def mixing_term(p_T: float, t: int) -> float:
    return math.sqrt((1.0 - p_T) ** (t + 1) / p_T)


def epsilon_bound(p_T: float, t: int, k: int, delta: float, N: int) -> float:
    """Theorem 1 at p_s = 1: mixing term plus ``sqrt(k / (delta N))``."""
    return mixing_term(p_T, t) + math.sqrt(k / (delta * N))


def plan(k: int, epsilon: float, delta: float, p_T: float, max_steps: int,
         R: int, L: int):
    """``(N, t, epsilon_bound)`` Theorem 1 gives a query: ``t`` from
    ``mixing <= eps/2`` (capped at ``max_steps`` and the index's reuse-free
    budget ``R L + L - 1``), ``N = ceil(4k / (delta eps^2))``."""
    target = (epsilon / 2.0) ** 2 * p_T
    t = (1 if target >= 1.0 else
         max(1, math.ceil(math.log(target) / math.log(1.0 - p_T) - 1.0)))
    t = min(t, max_steps, R * L + L - 1)
    N = max(1, math.ceil(4.0 * k / (delta * epsilon ** 2)))
    return N, t, epsilon_bound(p_T, t, k, delta, N)


class Verdict:
    """Numbers compared, each with its limit; correct iff none exceeds."""

    def __init__(self):
        self.numbers: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.numbers[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.numbers) and all(
            v["value"] <= v["limit"] for v in self.numbers.values())

    def lines(self) -> List[str]:
        return [f"check {k} {v['value']!r} limit {v['limit']!r}"
                for k, v in self.numbers.items()]


def answer_malformed(ans: dict, n: int, N: int, t: int, eps_bound: float
                     ) -> Optional[str]:
    """Why an answer breaks what its plan states, or None."""
    v = np.asarray(ans["vertices"])
    if len(v) != ans["k"]:
        return f"{len(v)} vertices, k = {ans['k']}"
    if len(set(v.tolist())) != len(v):
        return "repeated vertices"
    if v.min() < 0 or v.max() >= n:
        return "vertex out of range"
    if ans["num_walks"] != N:
        return f"{ans['num_walks']} walks, plan {N}"
    if ans["num_steps"] != t:
        return f"t = {ans['num_steps']}, plan {t}"
    if not math.isclose(ans["epsilon_bound"], eps_bound, rel_tol=1e-9):
        return f"epsilon_bound {ans['epsilon_bound']}, plan {eps_bound}"
    if ans["degraded"]:
        return "degraded"
    return None


def serving(answers: Sequence[dict], unanswered: int, row_ptr, col_idx,
            n: int, p_T: float, max_steps: int, R: int, L: int,
            seed: int, lim: dict) -> Verdict:
    """Judges PPR answers. Each answer carries its request (``source``,
    ``k``, ``epsilon``, ``delta`` as the traffic stated them) and the
    result's ``vertices``, ``scores``, ``num_walks``, ``num_steps``,
    ``epsilon_bound`` and ``degraded``. ``lim`` holds the configuration's
    limits (``limits/<configuration>.json``)."""
    verdict = Verdict()
    verdict.add("unanswered", unanswered, lim["unanswered"]["limit"])
    bad = 0
    for a in answers:
        N, t, eb = plan(a["k"], a["epsilon"], a["delta"], p_T, max_steps,
                        R, L)
        why = answer_malformed(a, n, N, t, eb)
        if why is not None:
            bad += 1
            if bad <= 3:
                print(f"malformed answer, source {a['source']}: {why}",
                      flush=True)
    verdict.add("malformed", bad, lim["malformed"]["limit"])
    by_source = {}
    for a in answers:
        by_source.setdefault(a["source"], a)
    distinct = sorted(by_source)
    if not distinct:
        return verdict
    r = np.random.default_rng([int(seed), 0xC0DE])
    pick = r.choice(len(distinct), size=min(SAMPLE_ANSWERS, len(distinct)),
                    replace=False)
    sample = [by_source[distinct[i]] for i in sorted(pick)]
    groups: Dict[tuple, List[dict]] = {}
    for a in sample:
        N, t, eb = plan(a["k"], a["epsilon"], a["delta"], p_T, max_steps,
                        R, L)
        groups.setdefault((N, t, eb), []).append(a)
    deficits, shortfall, bound = [], 0.0, math.inf
    for (N, t, eb), group in groups.items():
        n_ref = REF_WALKS_PER_WALK * N
        src = np.array([a["source"] for a in group]
                       + [group[0]["source"]] * (SAMPLE_ANSWERS - len(group)),
                       np.int32)
        key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % 2**32),
                                 0x5EF)
        ends = np.asarray(ref_walks.endpoints(
            row_ptr, col_idx, src, key, walks=n_ref, t=t, p_T=p_T))
        for j, a in enumerate(group):
            vals, counts = np.unique(ends[j], return_counts=True)
            ref = dict(zip(vals.tolist(), counts.tolist()))
            mu_k = np.sort(counts)[-a["k"]:].sum() / n_ref
            got = sum(ref.get(x, 0) for x in np.asarray(a["vertices"]).tolist()
                      ) / n_ref
            deficits.append(1.0 - got / mu_k)
            shortfall = max(shortfall, mu_k - got)
            bound = min(bound, eb)
    print(f"compared {len(sample)} answers with {REF_WALKS_PER_WALK} "
          f"reference walks per walk: mass deficit max "
          f"{max(deficits):.6f}", flush=True)
    verdict.add("ppr_mass_deficit", float(np.mean(deficits)),
                lim["ppr_mass_deficit"]["limit"])
    verdict.add("ppr_mass_shortfall", shortfall, bound)
    return verdict


def batch(estimates: Sequence[dict], pi_t: np.ndarray, pi: np.ndarray,
          N: int, t: int, p_T: float, k: int, delta: float, lim: dict
          ) -> Verdict:
    """Judges batch estimates, each ``{"counts", "pi_hat"}`` on the host,
    against the exact truncated-walk distribution ``pi_t`` and global
    PageRank ``pi`` of the same graph, under the limits ``lim``."""
    verdict = Verdict()
    lost = 0
    for e in estimates:
        c = np.asarray(e["counts"]).astype(np.int64)
        lost += abs(int(c.sum()) - N)
        # a division on the device may differ from the host's in the last
        # place, so pi_hat is held to counts / N within two f32 ulps
        want = c / N
        lost += int(np.count_nonzero(~np.isclose(
            np.asarray(e["pi_hat"], np.float64), want, rtol=2.4e-7, atol=0)))
    verdict.add("frogs_lost", lost, lim["frogs_lost"]["limit"])
    top = np.argsort(-pi_t, kind="stable")[:BATCH_TOP]
    p = pi_t[top]
    z2 = [((np.asarray(e["counts"])[top] - N * p) ** 2
           / (N * p * (1.0 - p))) for e in estimates]
    verdict.add("frog_count_z2", float(np.mean(z2)),
                lim["frog_count_z2"]["limit"])
    best = np.sort(pi)[-k:].sum()
    shortfall = max(
        best - pi[np.argsort(-np.asarray(e["pi_hat"]), kind="stable")[:k]
                  ].sum() for e in estimates)
    verdict.add("pagerank_mass_shortfall", shortfall,
                epsilon_bound(p_T, t, k, delta, N))
    return verdict
