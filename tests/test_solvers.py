"""Solver correctness: IHS / PCG / Polyak / CG / adaptive vs direct solve,
convergence-rate assertions (Thm 3.2 / eq. 3.3), and Theorem 4.1 bounds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AdaptiveConfig,
    adaptive_solve,
    cg_solve,
    direct_solve,
    factorize,
    from_least_squares,
    gram,
    k_max,
    make_sketch,
    run_fixed,
)
from repro.core.adaptive_padded import padded_adaptive_solve
from repro.core.effective_dim import m_delta_gaussian


def _rel_err(x, x_star):
    return float(jnp.linalg.norm(x - x_star) / jnp.linalg.norm(x_star))


@pytest.mark.parametrize("layout", ["shared", "per_problem",
                                    "weighted_shared", "weighted"])
def test_gram_matches_float64(layout):
    """The engine's chunked, compensated Gram against a float64 AᵀWA for
    every layout it serves, with n not a multiple of the chunk (the padded
    tail adds exact zeros)."""
    B, n, d = 3, 1000, 24
    kA, kw = jax.random.split(jax.random.PRNGKey(5))
    shared = layout in ("shared", "weighted_shared")
    A = jax.random.normal(kA, (n, d) if shared else (B, n, d))
    w = (jax.random.uniform(kw, (B, n)) if layout.startswith("weighted")
         else None)
    G = np.asarray(gram(A, w, chunk=256), np.float64)
    A64 = np.asarray(A, np.float64)
    if w is None:
        ref = (A64.T @ A64 if shared
               else np.einsum("bnd,bne->bde", A64, A64))
    else:
        w64 = np.asarray(w, np.float64)
        ref = (np.einsum("bn,nd,ne->bde", w64, A64, A64) if shared
               else np.einsum("bn,bnd,bne->bde", w64, A64, A64))
    assert G.shape == ref.shape
    np.testing.assert_allclose(G, ref, rtol=0, atol=1e-5 * np.abs(ref).max())


@pytest.mark.parametrize("method", ["ihs", "pcg", "polyak"])
@pytest.mark.parametrize("kind", ["gaussian", "srht", "sjlt"])
def test_fixed_sketch_converges(ridge_problem, method, kind):
    q, x_star = ridge_problem["q"], ridge_problem["x_star"]
    m = 4 * int(ridge_problem["d_e"])  # comfortably above d_e
    sk = make_sketch(kind, m, q.n, jax.random.PRNGKey(3))
    P = factorize(sk.apply(q.A), q.nu, q.lam_diag)
    x, trace = run_fixed(q, P, jnp.zeros((q.d,)), method=method,
                         iters=40, rho=0.5)
    assert _rel_err(x, x_star) < 1e-3
    # δ̃ decreased monotonically-ish (allow small numerical jitter at floor)
    tr = np.asarray(trace)
    assert tr[-1] < tr[0] * 1e-4


def test_ihs_rate_matches_theory(ridge_problem):
    """Thm 3.2: conditional on E_ρ, δ_t ≤ ρ^t δ_0. With m large the measured
    per-step contraction must beat the theoretical ρ for the effective
    deviation. Use m = n/2 (ρ_eff small)."""
    q, x_star = ridge_problem["q"], ridge_problem["x_star"]
    m = q.n // 2
    sk = make_sketch("gaussian", m, q.n, jax.random.PRNGKey(4))
    P = factorize(sk.apply(q.A), q.nu, q.lam_diag)
    rho = 0.5
    x, trace = run_fixed(q, P, jnp.zeros((q.d,)), method="ihs",
                         iters=10, rho=rho)
    tr = np.asarray(trace)
    ratios = tr[1:] / tr[:-1]
    # c(α,ρ)·φ(ρ) per-step bound on δ̃ ratios (Cor 2.5)
    assert np.all(ratios[:5] < (1 + np.sqrt(rho)) / (1 - np.sqrt(rho)) * rho)


def test_pcg_beats_ihs(ridge_problem):
    """PCG is optimal among preconditioned first-order methods (Thm 3.3)."""
    q = ridge_problem["q"]
    m = 2 * int(ridge_problem["d_e"])
    sk = make_sketch("gaussian", m, q.n, jax.random.PRNGKey(5))
    P = factorize(sk.apply(q.A), q.nu, q.lam_diag)
    x0 = jnp.zeros((q.d,))
    _, tr_pcg = run_fixed(q, P, x0, method="pcg", iters=15, rho=0.5)
    _, tr_ihs = run_fixed(q, P, x0, method="ihs", iters=15, rho=0.5)
    assert float(tr_pcg[-1]) <= float(tr_ihs[-1]) * 1.01


def test_cg_baseline(ridge_problem):
    q, x_star = ridge_problem["q"], ridge_problem["x_star"]
    x, _ = cg_solve(q, jnp.zeros((q.d,)), iters=600)
    assert _rel_err(x, x_star) < 1e-2


@pytest.mark.parametrize("method,sketch", [
    ("pcg", "sjlt"), ("pcg", "srht"), ("ihs", "gaussian"),
])
def test_adaptive_converges_and_bounds(ridge_problem, method, sketch):
    q, x_star = ridge_problem["q"], ridge_problem["x_star"]
    cfg = AdaptiveConfig(method=method, sketch=sketch, max_iters=200,
                         tol=1e-9)
    res = adaptive_solve(q, cfg, key=jax.random.PRNGKey(1))
    assert _rel_err(res.x, x_star) < 1e-2
    # Theorem 4.1: K_t ≤ K_max; m_t ≤ max(m_init, 2·m_δ/ρ) (and ≤ n cap)
    km = k_max(m_delta_gaussian(ridge_problem["d_e"]), cfg.rho, cfg.m_init)
    assert res.n_doublings <= max(km, int(np.ceil(np.log2(q.n))))
    assert res.m_final <= q.n


def test_adaptive_matrix_rhs(ridge_problem):
    """Multi-class (matrix) RHS — the paper's real-data setting."""
    q0 = ridge_problem["q"]
    c = 5
    Y = jax.random.normal(jax.random.PRNGKey(7), (q0.n, c))
    q = from_least_squares(q0.A, Y, q0.nu)
    X_star = direct_solve(q)
    res = adaptive_solve(
        q, AdaptiveConfig(method="pcg", sketch="sjlt", max_iters=100,
                          tol=1e-9),
        key=jax.random.PRNGKey(2),
    )
    assert _rel_err(res.x, X_star) < 1e-2


def test_padded_adaptive(ridge_problem):
    q, x_star = ridge_problem["q"], ridge_problem["x_star"]
    x, stats = padded_adaptive_solve(
        q, jax.random.PRNGKey(9), m_max=512, max_iters=100, rho=0.5,
        tol=1e-10,
    )
    assert _rel_err(x, x_star) < 1e-2
    assert int(stats["m_final"]) <= 512


def test_woodbury_vs_primal():
    """Dual (m<d) and primal (m≥d) factorizations solve the same system.
    ν = 0.3 keeps κ(H_S) ~ 10 so float32 residuals are meaningful; the
    small-ν regime is exercised end-to-end by the solver tests (where PCG
    self-corrects the f32 factorization error)."""
    n, d, nu = 1024, 256, 0.3
    A = jax.random.normal(jax.random.PRNGKey(10), (n, d)) / np.sqrt(n)
    q = from_least_squares(A, jnp.ones((n,)), nu)
    z = jax.random.normal(jax.random.PRNGKey(11), (q.d,))
    sk = make_sketch("gaussian", q.d // 2, q.n, jax.random.PRNGKey(12))
    SA = sk.apply(q.A)
    P_dual = factorize(SA, q.nu, q.lam_diag)
    assert P_dual.mode == "dual"
    H_S = SA.T @ SA + (q.nu ** 2) * jnp.diag(q.lam_diag)
    v = P_dual.solve(z)
    np.testing.assert_allclose(np.asarray(H_S @ v), np.asarray(z),
                               rtol=1e-3, atol=1e-3)
    # and the primal path agrees
    sk2 = make_sketch("gaussian", 2 * q.d, q.n, jax.random.PRNGKey(13))
    P_primal = factorize(sk2.apply(q.A), q.nu, q.lam_diag)
    assert P_primal.mode == "primal"
    v2 = P_primal.solve(z)
    H_S2 = sk2.apply(q.A).T @ sk2.apply(q.A) + (q.nu ** 2) * jnp.diag(q.lam_diag)
    np.testing.assert_allclose(np.asarray(H_S2 @ v2), np.asarray(z),
                               rtol=1e-3, atol=1e-3)
