"""The benchmark's graph generators, the per-seed relabelling and the
plain references, at small sizes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import store
from bench.graphs import chung_lu, kronecker
from bench.reference import pagerank, walks


def test_chung_lu_copy_matches_the_programs_generator():
    from repro.graph import chung_lu_powerlaw
    n, rp, ci = chung_lu.generate({"n": 20_000, "mean_out_degree": 14.23,
                                   "theta": 2.2, "seed": 3})
    g = chung_lu_powerlaw(n=20_000, avg_out_deg=14.23, theta=2.2, seed=3)
    assert np.array_equal(np.asarray(g.row_ptr), rp)
    assert np.array_equal(np.asarray(g.col_idx), ci)
    assert abs(len(ci) / n - 14.23) < 0.1


KRON = {"scale": 12, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "seed": 5}


def test_kronecker_edge_count_and_quadrant_shares_follow_the_spec():
    i, j = kronecker.edges(KRON)
    assert len(i) == 16 << 12
    assert i.max() < 1 << 12 and j.max() < 1 << 12
    # at scale 1 the label permutation at most swaps the two vertices, so
    # the share of edges with equal endpoints is quadrant A's plus D's
    ii, jj = kronecker.edges({"scale": 1, "edge_factor": 1 << 17, "a": 0.57,
                              "b": 0.19, "c": 0.19, "seed": 7})
    assert np.mean(ii == jj) == pytest.approx(0.57 + 0.05, abs=0.01)


def test_kronecker_cleanup_is_graphalytics():
    n, rp, ci = kronecker.generate(KRON)
    deg = np.diff(rp.astype(np.int64))
    src = np.repeat(np.arange(n), deg)
    assert np.all(deg > 0)                      # no isolated vertex
    assert not np.any(src == ci)                # no self-loop
    pairs = src.astype(np.int64) * n + ci
    assert len(np.unique(pairs)) == len(pairs)  # no duplicate arc
    back = ci.astype(np.int64) * n + src
    assert np.array_equal(np.sort(pairs), np.sort(back))   # undirected
    assert n < 1 << 12 and len(ci) < 2 * (16 << 12)
    assert np.all(np.diff(src) >= 0)            # CSR by source


def test_relabel_gives_the_same_graph_under_the_permutation():
    n, rp, ci = chung_lu.generate({"n": 500, "mean_out_degree": 5,
                                   "theta": 2.2, "seed": 1})
    nrp, nci, ndeg, perm = map(np.asarray, store.relabel(n, rp, ci, 2**31 + 9))
    assert sorted(perm.tolist()) == list(range(n))
    assert np.array_equal(ndeg, np.diff(nrp))
    for v in range(n):
        old = sorted(perm[ci[rp[v]:rp[v + 1]]].tolist())
        new = sorted(nci[nrp[perm[v]]:nrp[perm[v] + 1]].tolist())
        assert old == new
    other = np.asarray(store.relabel(n, rp, ci, 2**31 + 10)[3])
    assert not np.array_equal(perm, other)


def _dense_truncated(rp, ci, x0, t, p_T):
    n = len(rp) - 1
    P = np.zeros((n, n))
    for u in range(n):
        for v in ci[rp[u]:rp[u + 1]]:
            P[u, v] += 1.0 / (rp[u + 1] - rp[u])
    acc, x = np.zeros(n), np.asarray(x0, float)
    for m in range(t):
        acc += p_T * (1 - p_T) ** m * x
        x = x @ P
    return acc + (1 - p_T) ** t * x


def test_truncated_walk_matches_the_dense_matrix():
    n, rp, ci = chung_lu.generate({"n": 200, "mean_out_degree": 4,
                                   "theta": 2.2, "seed": 2})
    x0 = np.zeros(n)
    x0[7] = 1.0
    got = pagerank.truncated_walk(rp, ci, x0, 12, 0.15)
    assert np.allclose(got, _dense_truncated(rp, ci, x0, 12, 0.15))
    pi = pagerank.global_pagerank(rp, ci, 0.15)
    assert pi.sum() == pytest.approx(1.0)
    assert np.allclose(pi, _dense_truncated(rp, ci, np.full(n, 1 / n), 300,
                                            0.15), atol=1e-9)


def test_reference_walks_sample_the_truncated_ppr():
    n, rp, ci = chung_lu.generate({"n": 300, "mean_out_degree": 4,
                                   "theta": 2.2, "seed": 4})
    src = np.array([3, 77], np.int32)
    ends = np.asarray(walks.endpoints(jnp.asarray(rp), jnp.asarray(ci),
                                      jnp.asarray(src), jax.random.PRNGKey(0),
                                      walks=200_000, t=8, p_T=0.15))
    for j, s in enumerate(src):
        x0 = np.zeros(n)
        x0[s] = 1.0
        want = pagerank.truncated_walk(rp, ci, x0, 8, 0.15)
        got = np.bincount(ends[j], minlength=n) / ends.shape[1]
        assert 0.5 * np.abs(got - want).sum() < 0.02
