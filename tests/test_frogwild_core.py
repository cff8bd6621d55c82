"""FrogWild! oracle invariants + paper-claim validation (single device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    FrogWildConfig,
    frogwild,
    normalized_mass_captured,
    exact_identification,
    power_iteration,
    theory,
)
from repro.core.pagerank import pagerank_residual
from repro.graph import chung_lu_powerlaw, ring_of_cliques, uniform_random


@pytest.fixture(scope="module")
def graph_and_pi():
    g = chung_lu_powerlaw(n=1500, avg_out_deg=10, seed=1)
    pi = power_iteration(g, num_iters=60)
    return g, pi


@given(
    n=st.integers(30, 120),
    N=st.integers(100, 2000),
    t=st.integers(1, 6),
    p_s=st.sampled_from([1.0, 0.7, 0.3]),
    erasure=st.sampled_from(["none", "independent", "channel"]),
)
@settings(max_examples=15)
def test_frog_conservation(n, N, t, p_s, erasure):
    """Every frog is tallied exactly once — the core system invariant.

    (Example-10 repair means no frog is ever lost, unlike Example 9 alone —
    paper footnote 1.)"""
    g = uniform_random(n, avg_out_deg=4, seed=0)
    cfg = FrogWildConfig(num_frogs=N, num_steps=t, p_s=p_s,
                         erasure="none" if p_s == 1.0 else erasure,
                         num_shards=4)
    res = frogwild(g, cfg, seed=1)
    assert int(res.counts.sum()) == N
    assert float(res.pi_hat.sum()) == pytest.approx(1.0, abs=1e-5)
    assert (np.asarray(res.counts) >= 0).all()


def test_estimator_converges_to_pagerank(graph_and_pi):
    """Lemma 16 + Chernoff: π̂ → π for many frogs and enough steps."""
    g, pi = graph_and_pi
    cfg = FrogWildConfig(num_frogs=300_000, num_steps=24, p_s=1.0)
    res = frogwild(g, cfg, seed=0)
    l1 = float(jnp.abs(res.pi_hat - pi).sum())
    assert l1 < 0.12, l1                      # sampling noise at N=300k
    assert float(normalized_mass_captured(res.pi_hat, pi, 20)) > 0.97


def test_partial_sync_graceful_degradation(graph_and_pi):
    """Paper Fig 2: accuracy degrades gracefully as p_s drops."""
    g, pi = graph_and_pi
    masses = {}
    for p_s in (1.0, 0.4, 0.1):
        cfg = FrogWildConfig(num_frogs=100_000, num_steps=8, p_s=p_s,
                             erasure="channel", num_shards=16)
        res = frogwild(g, cfg, seed=2)
        masses[p_s] = float(normalized_mass_captured(res.pi_hat, pi, 50))
    assert masses[1.0] > 0.95
    assert masses[0.4] > 0.85
    assert masses[0.1] > 0.55
    assert masses[1.0] >= masses[0.1]


def test_theorem1_bound_holds(graph_and_pi):
    """μ_k(π̂) > μ_k(π) − ε with the paper's ε (Theorem 1)."""
    g, pi = graph_and_pi
    k, t, N, p_s, delta = 20, 12, 200_000, 0.7, 0.1
    cfg = FrogWildConfig(num_frogs=N, num_steps=t, p_s=p_s,
                         erasure="channel", num_shards=8)
    pi_inf = float(pi.max())
    p_cap = theory.p_cap_bound(g.n, t, pi_inf, 0.15)
    eps = theory.epsilon_bound(0.15, t, k, delta, N, p_s, p_cap)
    res = frogwild(g, cfg, seed=3)
    from repro.core.metrics import mass_captured

    mu_hat = float(mass_captured(res.pi_hat, pi, k))
    _, idx = jax.lax.top_k(pi, k)
    mu_opt = float(pi[idx].sum())
    assert mu_hat > mu_opt - eps


def test_power_iteration_fixed_point():
    g = chung_lu_powerlaw(n=500, avg_out_deg=8, seed=5)
    pi = power_iteration(g, num_iters=80)
    assert float(pagerank_residual(g, pi)) < 1e-5
    assert float(pi.sum()) == pytest.approx(1.0, abs=1e-5)
    assert float(pi.min()) >= 0.15 / g.n * 0.99   # teleport floor


@pytest.mark.parametrize("n", [500, 777])
def test_power_iteration_ell_matches_coo(n):
    """The hybrid-ELL Pallas SpMV path (slab kernel + hub spill tail) runs
    the same power iteration as the COO segment-sum path."""
    g = chung_lu_powerlaw(n=n, avg_out_deg=8, seed=5)
    ell = power_iteration(g, num_iters=50, spmv="ell")
    coo = power_iteration(g, num_iters=50, spmv="coo")
    np.testing.assert_allclose(np.asarray(ell), np.asarray(coo), atol=1e-5)


def test_power_iteration_matches_dense_eig():
    g = ring_of_cliques(3, 4)
    pi = power_iteration(g, num_iters=200)
    from repro.graph.csr import adjacency_dense

    P = adjacency_dense(g)
    Q = 0.85 * P + 0.15 / g.n
    evals, evecs = np.linalg.eig(Q)
    i = np.argmax(evals.real)
    v = np.abs(evecs[:, i].real)
    v /= v.sum()
    np.testing.assert_allclose(np.asarray(pi), v, atol=1e-4)


def test_reduced_iterations_is_worse_than_frogwild_time_budget(graph_and_pi):
    """The paper's core claim, shape-level: a 1-iteration PR baseline is a
    *worse* approximation than FrogWild with a modest frog budget."""
    g, pi = graph_and_pi
    pr1 = power_iteration(g, num_iters=1)
    cfg = FrogWildConfig(num_frogs=200_000, num_steps=8, p_s=1.0)
    fw = frogwild(g, cfg, seed=4)
    k = 50
    m_pr1 = float(normalized_mass_captured(pr1, pi, k))
    m_fw = float(normalized_mass_captured(fw.pi_hat, pi, k))
    assert m_fw > m_pr1


@given(t=st.integers(1, 40))
def test_theory_mixing_term_decreases(t):
    assert theory.mixing_term(0.15, t + 1) < theory.mixing_term(0.15, t)


@given(N=st.integers(10, 10_000), k=st.integers(1, 50))
def test_theory_sampling_term_monotone(N, k):
    a = theory.sampling_term(k, 0.1, N, 1.0, 0.0)
    b = theory.sampling_term(k, 0.1, 2 * N, 1.0, 0.0)
    assert b < a
    assert theory.sampling_term(k + 1, 0.1, N, 1.0, 0.0) > a


def test_theory_suggestions_sane():
    assert theory.suggested_steps(0.1) >= 1
    assert theory.suggested_frogs(100, 0.3) >= 100


_WALK = dict(num_frogs=1500, num_steps=10)


@pytest.mark.parametrize("cfg", [
    FrogWildConfig(**_WALK),
    FrogWildConfig(**_WALK, step_impl="stream"),
    FrogWildConfig(**_WALK, p_s=0.6, erasure="channel", num_shards=4,
                   draw="rejection"),
    FrogWildConfig(**_WALK, p_s=0.6, erasure="channel", num_shards=4,
                   draw="cumsum"),
    FrogWildConfig(**_WALK, p_s=0.6, erasure="independent", draw="cumsum"),
], ids=["plain", "stream", "channel-rejection", "channel-cumsum",
        "independent-cumsum"])
def test_walker_with_graph_operands_matches_closed_over_graph(cfg):
    """``frogwild_walks`` passes the CSR and the layouts derived from it as
    operands; the answer is the one of the same walk with the concrete
    graph closed over."""
    from repro.core.frogwild import _as_tuple, _frogwild_walks, frogwild_walks

    g = chung_lu_powerlaw(n=400, avg_out_deg=6, seed=5)
    key = jax.random.PRNGKey(7)
    counts, pi_hat = jax.jit(
        lambda k: _as_tuple(_frogwild_walks(g, cfg, k)))(key)
    res = frogwild_walks(g, cfg, key)
    assert np.array_equal(np.asarray(res.counts), np.asarray(counts))
    assert np.array_equal(np.asarray(res.pi_hat), np.asarray(pi_hat))
    assert int(np.asarray(res.counts).sum()) == cfg.num_frogs
