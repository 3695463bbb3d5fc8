"""The padded engine's dual (m×m) ladder form (DESIGN.md §6).

When every ladder level has m < d, the family's levels are prefixes of one
row stream R, and the call has no mesh and no ``grams=``, the engine keeps
U_l = L_l⁻¹(R_l·s) per level, L_lL_lᵀ = m_l·I + (R_l·s)(R_l·s)ᵀ and
s = (ν²Λ)^{-1/2}, instead of a d×d inverse, and applies
H_S⁻¹z = s ⊙ (y − U_lᵀU_l y), y = s ⊙ z. Checked here on the CPU at small
shapes: the dual application against the primal inverse at every level,
dual solves against primal solves of the same sketch (the ``grams=`` path
stays primal), the guard verdicts, the rule that picks the form, and that
every primal call is bit for bit what it was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adaptive_padded import (
    _apply_pinv, doubling_ladder, finalize_padded_solve,
    padded_adaptive_solve_batched, padded_solve_segment, prepare_padded_solve,
    prepare_path_ladder, reprecondition_padded)
from repro.core.level_grams import (BlockEmulationProvider, get_provider,
                                    prefix_level_grams)
from repro.core.newton import adaptive_newton_solve_batched, irls_reference
from repro.core.precond import shifted_ladder_inverses
from repro.core.quadratic import Quadratic
from repro.core.status import SolveStatus
from repro.ft.faults import AdversarialKeyProvider

N, D, M_MAX, B = 512, 96, 32, 2
PREFIX_FAMILIES = ("gaussian", "gaussian_dense", "srht")


def _problem(*, weighted=False, lam=False, nu=(1e-2, 3e-2), seed=0):
    """A = (Z/√n)·diag(σ)·Vᵀ with V a random rotation and
    σ_j = 0.995^(j·4096/d): the benchmark cell's problem (d = 4096)
    squeezed into d columns, so d_e/d (≈ 0.22 at ν = 1e-2) and cond(H)
    (≈ 1e4) are the cell's; b = AᵀWy; optional row weights and a
    non-identity Λ."""
    kA, kV, ky, kw, kl = jax.random.split(jax.random.key(seed), 5)
    V, _ = jnp.linalg.qr(jax.random.normal(kV, (D, D)))
    A = (jax.random.normal(kA, (B, N, D))
         * 0.995 ** (jnp.arange(D) * 4096 / D) / N ** 0.5) @ V.T
    y = jax.random.normal(ky, (B, N))
    w = (jax.random.uniform(kw, (B, N), minval=0.2, maxval=2.0)
         if weighted else None)
    lam_diag = (jax.random.uniform(kl, (B, D), minval=0.5, maxval=2.0)
                if lam else jnp.ones((B, D)))
    yw = y if w is None else w * y
    return Quadratic(A=A, b=jnp.einsum("bnd,bn->bd", A, yw),
                     nu=jnp.asarray(nu, jnp.float32), lam_diag=lam_diag,
                     batched=True, row_weights=w)


def _keys(seed=1):
    return jax.random.split(jax.random.key(seed), B)


def _x64_solution(q):
    A = np.asarray(q.A, np.float64)
    w = (np.ones((B, N)) if q.row_weights is None
         else np.asarray(q.row_weights, np.float64))
    out = []
    for i in range(B):
        H = A[i].T @ (w[i][:, None] * A[i]) + np.diag(
            float(q.nu[i]) ** 2 * np.asarray(q.lam_diag[i], np.float64))
        out.append(np.linalg.solve(H, np.asarray(q.b[i], np.float64)))
    return np.stack(out)


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float64) - b)
                 / np.linalg.norm(b))


@pytest.mark.parametrize("sketch", PREFIX_FAMILIES)
def test_dual_application_matches_primal_inverse_at_every_level(sketch):
    """With row weights and Λ ≠ I: the engine's dual P z at each level is
    (G_l + ν²Λ)⁻¹z, G_l being ``prefix_level_grams`` of the family's rows
    and the inverse ``shifted_ladder_inverses``, taken in float64. In the
    top directions of H_l, where y − UᵀU y cancels, the fp32 error stays
    within a small factor of the fp32 primal inverse's own."""
    q = _problem(weighted=True, lam=True)
    keys = _keys()
    ladder = doubling_ladder(M_MAX)
    pre, _ = prepare_padded_solve(q, keys, m_max=M_MAX, sketch=sketch)
    assert pre.dscale is not None and pre.pinvs.shape == (
        len(ladder), B, M_MAX, D)
    prov = get_provider(sketch)
    R = prov.level_rows(prov.sample(keys, M_MAX, N, jnp.float32), q, M_MAX)
    grams = prefix_level_grams(R, ladder, inv_m_scale=True)
    inv32 = shifted_ladder_inverses(grams, q.nu, q.lam_diag)
    with jax.enable_x64(True):
        grams64 = prefix_level_grams(jnp.asarray(np.asarray(R), jnp.float64),
                                     ladder, inv_m_scale=True)
        inv64 = np.asarray(shifted_ladder_inverses(
            grams64, jnp.asarray(np.asarray(q.nu), jnp.float64),
            jnp.asarray(np.asarray(q.lam_diag), jnp.float64)))
    z_random = np.asarray(jax.random.normal(jax.random.key(7), (B, D)))
    for level in range(len(ladder)):
        # a random z, and the sum of the three top eigendirections of H_l
        top = np.linalg.eigh(np.linalg.inv(inv64[level]))[1][:, :, -3:]
        for z in (z_random, top.sum(axis=-1)):
            want = np.einsum("bde,be->bd", inv64[level], z)
            z32 = jnp.asarray(z, jnp.float32)
            got = np.asarray(_apply_pinv(pre, pre.pinvs[level], z32))
            primal = np.asarray(jnp.einsum("bde,be->bd", inv32[level], z32))
            for i in range(B):
                assert _rel(got[i], want[i]) < (
                    4 * _rel(primal[i], want[i]) + 1e-6), (level, i)


@pytest.mark.parametrize("method,m_max,nu", [
    ("pcg", M_MAX, (1e-2, 3e-2)),
    # the momentum-free methods need m ≫ d_e, hence a larger ν and m_max
    ("ihs", 2 * M_MAX, (3e-2, 5e-2)),
    ("polyak", 2 * M_MAX, (3e-2, 5e-2))])
@pytest.mark.parametrize("sketch", PREFIX_FAMILIES)
def test_dual_solve_matches_primal_solve_of_the_same_sketch(sketch, method,
                                                            m_max, nu):
    q = _problem(weighted=sketch == "gaussian", lam=sketch == "srht", nu=nu)
    keys = _keys()
    kw = dict(m_max=m_max, method=method, sketch=sketch, max_iters=300,
              tol=1e-10)
    x_d, s_d = padded_adaptive_solve_batched(q, keys, **kw)
    grams, gram_full = prepare_path_ladder(q, keys, m_max=m_max,
                                           sketch=sketch)
    x_p, s_p = padded_adaptive_solve_batched(q, keys, grams=grams,
                                             gram_full=gram_full, **kw)
    assert np.asarray(s_d["ladder_dual"]).all()
    assert not np.asarray(s_p["ladder_dual"]).any()
    x64 = _x64_solution(q)
    for i in range(B):
        assert _rel(x_d[i], x64[i]) < 2e-3, i
        assert _rel(x_p[i], x64[i]) < 2e-3, i
    np.testing.assert_array_equal(s_d["status"], s_p["status"])
    assert (np.asarray(s_d["status"]) == int(SolveStatus.OK)).all()
    np.testing.assert_array_equal(s_d["m_final"], s_p["m_final"])
    assert np.abs(np.asarray(s_d["iters"]) - np.asarray(s_p["iters"])
                  ).max() <= 2


def test_nan_in_A_is_nan_poisoned_in_dual_form():
    q = _problem()
    q = Quadratic(A=q.A.at[1, 5, 3].set(jnp.nan), b=q.b, nu=q.nu,
                  lam_diag=q.lam_diag, batched=True)
    x, s = padded_adaptive_solve_batched(q, _keys(), m_max=M_MAX,
                                         method="pcg")
    assert np.asarray(s["ladder_dual"]).all()
    status = np.asarray(s["status"])
    assert status[1] == int(SolveStatus.NAN_POISONED)
    assert status[0] == int(SolveStatus.OK)
    assert np.isfinite(np.asarray(x)).all()


def test_all_invalid_ladder_is_level_invalid_in_both_forms():
    """A = 0, ν = 0: no level factorizes in either form — LEVEL_INVALID at
    x₀ = 0, with the same finite certificate (both fall back to P = I)."""
    q = Quadratic(A=jnp.zeros((B, N, D)), b=jnp.ones((B, D)),
                  nu=jnp.zeros((B,)), lam_diag=jnp.ones((B, D)),
                  batched=True)
    keys = _keys()
    x_d, s_d = padded_adaptive_solve_batched(q, keys, m_max=M_MAX)
    grams, _ = prepare_path_ladder(q, keys, m_max=M_MAX)
    x_p, s_p = padded_adaptive_solve_batched(q, keys, m_max=M_MAX,
                                             grams=grams)
    assert np.asarray(s_d["ladder_dual"]).all()
    for x, s in ((x_d, s_d), (x_p, s_p)):
        assert (np.asarray(s["status"]) == int(SolveStatus.LEVEL_INVALID)
                ).all()
        assert bool(jnp.all(x == 0.0))
    np.testing.assert_array_equal(s_d["dtilde"], s_p["dtilde"])
    np.testing.assert_array_equal(s_d["invalid_levels"], s_p["invalid_levels"])


def _adversarial():
    return AdversarialKeyProvider("gaussian", _keys(9)[:1])


@pytest.mark.parametrize("sketch,m_max,given_grams,dual", [
    ("gaussian", M_MAX, False, True),
    ("gaussian_dense", M_MAX, False, True),
    ("srht", M_MAX, False, True),
    ("gaussian", D, False, False),               # m_max = d
    ("gaussian", 2 * D, False, False),           # a service shape class
    ("sjlt", M_MAX, False, False),               # folds, not prefixes
    ("gaussian", M_MAX, True, False),            # path mode's grams=
    ("block", M_MAX, False, False),              # a summed block sketch
    ("adversarial", M_MAX, False, False),        # a wrapper with no rows
])
def test_ladder_dual_follows_the_rule(sketch, m_max, given_grams, dual):
    q, keys = _problem(), _keys()
    prov = {"block": lambda: BlockEmulationProvider("gaussian", 2),
            "adversarial": _adversarial}.get(sketch, lambda: sketch)()
    kw = dict(m_max=m_max, sketch=prov, method="pcg", max_iters=5)
    if given_grams:
        kw["grams"] = prepare_path_ladder(q, keys, m_max=m_max,
                                          sketch=prov)[0]
    _, s = padded_adaptive_solve_batched(q, keys, **kw)
    np.testing.assert_array_equal(s["ladder_dual"], np.full((B,), dual))


@pytest.mark.parametrize("sketch,m_max", [
    ("gaussian", 2 * D), ("srht", 2 * D), ("sjlt", 2 * D), ("sjlt", M_MAX)])
def test_primal_calls_equal_the_grams_path_bit_for_bit(sketch, m_max):
    """The calls that keep the primal form run the code they ran before
    the dual form existed: the same numbers as handing the engine that
    sketch's level Grams, bit for bit."""
    q, keys = _problem(nu=(0.05, 0.1)), _keys()
    kw = dict(m_max=m_max, method="pcg", sketch=sketch, tol=1e-10,
              gram_hvp=True)
    x, s = padded_adaptive_solve_batched(q, keys, **kw)
    grams, gram_full = prepare_path_ladder(q, keys, m_max=m_max,
                                           sketch=sketch, gram_hvp=True)
    x_g, s_g = padded_adaptive_solve_batched(q, keys, grams=grams,
                                             gram_full=gram_full, **kw)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_g))
    assert s.keys() == s_g.keys()
    for k in s:
        np.testing.assert_array_equal(np.asarray(s[k]), np.asarray(s_g[k]),
                                      err_msg=k)
    assert not np.asarray(s["ladder_dual"]).any()


def test_dual_segments_and_reprecondition():
    """Segments of a dual-form solve are bitwise the monolithic solve; a
    mid-solve re-precondition from level Grams turns the state primal and
    still converges."""
    q, keys = _problem(), _keys()
    kw = dict(method="pcg", max_iters=200, tol=1e-10)
    x_mono, s_mono = padded_adaptive_solve_batched(q, keys, m_max=M_MAX,
                                                   **kw)
    pre, st = prepare_padded_solve(q, keys, m_max=M_MAX, tol=1e-10)
    assert st.pinv.shape == (B, M_MAX, D)
    for limit in (7, 19, 10_000):
        st = padded_solve_segment(q, pre, st, limit, **kw)
    x_seg, s_seg = finalize_padded_solve(pre, st, m_max=M_MAX)
    np.testing.assert_array_equal(np.asarray(x_mono), np.asarray(x_seg))
    for k in s_mono:
        np.testing.assert_array_equal(np.asarray(s_mono[k]),
                                      np.asarray(s_seg[k]), err_msg=k)

    pre, st = prepare_padded_solve(q, keys, m_max=M_MAX, tol=1e-10)
    st = padded_solve_segment(q, pre, st, 5, **kw)
    grams, _ = prepare_path_ladder(q, keys, m_max=M_MAX)
    pre2, st = reprecondition_padded(q, pre, st, grams)
    assert pre2.dscale is None and st.pinv.shape == (B, D, D)
    st = padded_solve_segment(q, pre2, st, 10_000, **kw)
    x, s = finalize_padded_solve(pre2, st, m_max=M_MAX)
    assert (np.asarray(s["status"]) == int(SolveStatus.OK)).all()
    assert not np.asarray(s["ladder_dual"]).any()
    x64 = _x64_solution(q)
    for i in range(B):
        assert _rel(x[i], x64[i]) < 2e-3, i


def test_newton_runs_the_weighted_dual_form():
    """The sketched-Newton GLM solve's weighted subproblems at m_max < d
    take the dual form and reach the IRLS answer."""
    kA, ky = jax.random.split(jax.random.key(3))
    n, d = 256, 48
    A = jax.random.normal(kA, (B, n, d)) / n ** 0.5
    Y = (jax.random.uniform(ky, (B, n)) < 0.5).astype(jnp.float32)
    inner = Quadratic(A=A, b=jnp.ones((B, d)), nu=jnp.full((B,), 0.1),
                      lam_diag=jnp.ones((B, d)), batched=True,
                      row_weights=jnp.full((B, n), 0.25))
    _, s = padded_adaptive_solve_batched(inner, _keys(), m_max=16,
                                         max_iters=3)
    assert np.asarray(s["ladder_dual"]).all()
    x, stats = adaptive_newton_solve_batched("logistic", A, Y, 0.1,
                                             m_max=16,
                                             keys=jax.random.PRNGKey(4))
    x_ref = irls_reference("logistic", A, Y, 0.1)
    assert np.asarray(stats["converged"]).all()
    assert float(jnp.max(jnp.abs(x - x_ref))) < 1e-4
