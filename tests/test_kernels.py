"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph import chung_lu_powerlaw, to_ell, uniform_random
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# Pallas mode follows the backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,interpret,want", [
    ("cpu", None, True), ("cpu", True, True), ("cpu", False, False),
    ("tpu", None, False), ("tpu", False, False),
])
def test_resolve_interpret_follows_backend(monkeypatch, backend, interpret,
                                           want):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    assert ops.resolve_interpret(interpret) is want


def test_resolve_interpret_refuses_host_interpreter_on_tpu(monkeypatch):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        ops.resolve_interpret(True)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' is neither"):
        ops.resolve_interpret()


def test_compiled_mode_refuses_kernels_mosaic_rejects():
    """Asked for explicitly in compiled mode, a kernel of ops.TPU_REFUSED
    raises with the compiler's reason instead of failing inside a
    larger program's lowering."""
    g = uniform_random(64, avg_out_deg=4, seed=0)
    pos = jnp.zeros(16, jnp.int32)
    slab = jnp.zeros((64, 4), jnp.int32)
    calls = {
        "frog_step": lambda: ops.frog_step(
            pos, pos, pos, g.row_ptr, g.col_idx, g.out_deg, g.n,
            impl="pallas", interpret=False),
        "frog_step_stream": lambda: ops.frog_step(
            pos, pos, pos, g.row_ptr, g.col_idx, g.out_deg, g.n,
            impl="stream", interpret=False),
        "stitch_step": lambda: ops.stitch_step(
            pos, pos, pos, slab, 64, interpret=False),
        "stitch_gather": lambda: ops.stitch_step(
            pos, pos, pos, slab, 64, interpret=False, tally=False),
        "stitch_step_local": lambda: ops.stitch_step_local(
            pos, pos, pos, slab[:32], 0, interpret=False),
        "stitch_gather_local": lambda: ops.stitch_step_local(
            pos, pos, pos, slab[:32], 0, interpret=False, tally=False),
        "spmv_ell": lambda: ops.spmv(to_ell(g, K=8), jnp.ones(64),
                                     interpret=False),
    }
    assert set(calls) == ops.TPU_REFUSED
    for kernel, call in calls.items():
        with pytest.raises(NotImplementedError,
                           match=f"{kernel}.*Only 2D gather is supported"):
            call()


def test_auto_step_avoids_kernels_that_do_not_compile():
    """impl="auto" in compiled mode picks the jnp path — byte-identical to
    impl="ref" — because neither fused step kernel compiles for TPU."""
    g = uniform_random(128, avg_out_deg=5, seed=1)
    rng = np.random.default_rng(0)
    pos = jnp.asarray(rng.integers(0, g.n, 64), jnp.int32)
    die = jnp.asarray(rng.integers(0, 2, 64), jnp.int32)
    bits = jnp.asarray(rng.integers(0, 1 << 30, 64), jnp.int32)
    args = (pos, die, bits, g.row_ptr, g.col_idx, g.out_deg, g.n)
    got = ops.frog_step(*args, impl="auto", interpret=False)
    want = ops.frog_step(*args, impl="ref")
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# SpMV (hybrid ELL)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,deg,K", [
    (256, 4, 8), (300, 10, 16), (1000, 12, 32), (513, 30, 16),
])
def test_spmv_matches_ref(n, deg, K):
    g = chung_lu_powerlaw(n=n, avg_out_deg=deg, seed=n)
    ell = to_ell(g, K=K)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(ell.n_rows),
                    dtype=jnp.float32)
    y_pal = ops.spmv(ell, x, impl="pallas")
    y_ref = ops.spmv(ell, x, impl="ref")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-4)


def test_spmv_hub_spill():
    """Power-law hubs overflow the slab — spill path must stay exact."""
    g = chung_lu_powerlaw(n=400, avg_out_deg=20, seed=9)
    ell = to_ell(g, K=8)          # tiny slab forces heavy spill
    assert ell.spill_nnz > 0
    x = jnp.ones((ell.n_rows,), jnp.float32)
    y = ops.spmv(ell, x, impl="pallas")
    # P is column-stochastic: sum of y equals number of real vertices' mass
    assert float(y[: g.n].sum()) == pytest.approx(g.n, rel=1e-4)


# ---------------------------------------------------------------------------
# frog_count histogram
# ---------------------------------------------------------------------------

@given(
    n=st.integers(8, 2000),
    N=st.integers(1, 5000),
    seed=st.integers(0, 100),
)
@settings(max_examples=15)
def test_frog_count_matches_ref(n, N, seed):
    dest = jnp.asarray(
        np.random.default_rng(seed).integers(0, n, size=N), dtype=jnp.int32)
    a = ops.frog_count(dest, n, impl="pallas")
    b = ops.frog_count(dest, n, impl="ref")
    assert (np.asarray(a) == np.asarray(b)).all()
    assert int(a.sum()) == N


def test_frog_count_skewed():
    dest = jnp.zeros((4096,), jnp.int32)          # all frogs on vertex 0
    c = ops.frog_count(dest, 1024, impl="pallas")
    assert int(c[0]) == 4096 and int(c.sum()) == 4096


@given(
    n=st.integers(8, 2000),
    N=st.integers(1, 5000),
    seed=st.integers(0, 100),
)
@settings(max_examples=10)
def test_frog_count_sort_matches_ref(n, N, seed):
    dest = jnp.asarray(
        np.random.default_rng(seed).integers(0, n, size=N), dtype=jnp.int32)
    a = ops.frog_count(dest, n, impl="sort")
    b = ops.frog_count(dest, n, impl="ref")
    assert (np.asarray(a) == np.asarray(b)).all()


def test_frog_count_sort_ignores_padding():
    dest = jnp.asarray([-1, 0, 3, 3, -1, 7], jnp.int32)
    c = np.asarray(ops.frog_count(dest, 8, impl="sort"))
    assert c.tolist() == [1, 0, 0, 2, 0, 0, 0, 1]


# ---------------------------------------------------------------------------
# fused frog_step (plain walker superstep)
# ---------------------------------------------------------------------------

@given(
    n=st.integers(16, 800),
    N=st.integers(8, 4000),
    seed=st.integers(0, 50),
)
@settings(max_examples=10)
def test_frog_step_matches_ref(n, N, seed):
    g = uniform_random(n, avg_out_deg=5, seed=seed)
    rng = np.random.default_rng(seed)
    pos = jnp.asarray(rng.integers(0, n, N), jnp.int32)
    die = jnp.asarray(rng.random(N) < 0.2, jnp.int32)
    bits = jnp.asarray(rng.integers(0, 1 << 30, N), jnp.int32)
    nxt_p, cnt_p = ops.frog_step(
        pos, die, bits, g.row_ptr, g.col_idx, g.out_deg, g.n, impl="pallas")
    nxt_r, cnt_r = ops.frog_step(
        pos, die, bits, g.row_ptr, g.col_idx, g.out_deg, g.n, impl="ref")
    assert (np.asarray(nxt_p) == np.asarray(nxt_r)).all()
    assert (np.asarray(cnt_p) == np.asarray(cnt_r)).all()
    assert int(cnt_p.sum()) == int(die.sum())


def test_frog_step_dangling_stays_put():
    # vertex 1 dangling: frogs there must not move or crash
    row_ptr = jnp.asarray([0, 1, 1], jnp.int32)
    col_idx = jnp.asarray([1], jnp.int32)
    deg = jnp.asarray([1, 0], jnp.int32)
    pos = jnp.asarray([0, 1, 1, 0], jnp.int32)
    die = jnp.zeros((4,), jnp.int32)
    bits = jnp.asarray([5, 9, 13, 2], jnp.int32)
    for impl in ("pallas", "ref"):
        nxt, cnt = ops.frog_step(pos, die, bits, row_ptr, col_idx, deg, 2,
                                 impl=impl)
        assert np.asarray(nxt).tolist() == [1, 1, 1, 1], impl
        assert int(cnt.sum()) == 0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

CASES = [
    # B, Hq, Hkv, S, D, window, causal, soft_cap, dtype
    (1, 4, 4, 256, 64, None, True, None, jnp.float32),
    (2, 4, 2, 256, 64, None, True, None, jnp.float32),
    (2, 8, 2, 384, 32, None, True, None, jnp.bfloat16),
    (1, 4, 1, 256, 128, None, False, None, jnp.float32),
    (2, 4, 2, 256, 64, 64, True, None, jnp.float32),
    (1, 2, 2, 512, 64, 128, True, None, jnp.float32),
    (1, 4, 4, 256, 64, None, True, 30.0, jnp.float32),
]


@pytest.mark.parametrize("B,Hq,Hkv,S,D,window,causal,cap,dtype", CASES)
def test_flash_attention_matches_ref(B, Hq, Hkv, S, D, window, causal, cap,
                                     dtype):
    rng = np.random.default_rng(B * 100 + S)
    q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype=dtype)
    out = ops.attention(q, k, v, causal=causal, window=window, soft_cap=cap,
                        impl="pallas")
    want = ops.attention(q, k, v, causal=causal, window=window, soft_cap=cap,
                         impl="ref")
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("window", [None, 64, 100])
def test_chunked_attention_matches_ref(window):
    rng = np.random.default_rng(0)
    B, Hq, Hkv, S, D = 2, 4, 2, 384, 32
    q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype=jnp.float32)
    out = ops.attention(q, k, v, causal=True, window=window,
                        impl="jnp_flash", chunk=128)
    want = ops.attention(q, k, v, causal=True, window=window, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_decode_attention_ref_consistency():
    rng = np.random.default_rng(1)
    B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)), dtype=jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype=jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype=jnp.float32)
    L = 77
    out = ref.decode_attention_ref(q, kc, vc, jnp.asarray(L))
    want = ref.attention_ref(q, kc[:, :, :L], vc[:, :, :L], causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@given(length=st.integers(1, 127), window=st.integers(1, 64))
@settings(max_examples=10)
def test_decode_attention_windowed(length, window):
    rng = np.random.default_rng(length)
    B, Hq, Hkv, S, D = 1, 2, 2, 128, 16
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)), dtype=jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype=jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype=jnp.float32)
    out = ref.decode_attention_ref(q, kc, vc, jnp.asarray(length),
                                   window=window)
    lo = max(0, length - window)
    want = ref.attention_ref(q, kc[:, :, lo:length], vc[:, :, lo:length],
                             causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
