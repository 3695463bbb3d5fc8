"""Adaptive sketched-Newton driver for regularized GLMs (DESIGN.md §8).

Outer loop: damped Newton with backtracking line search on

    F(x) = Σ_i ℓ(a_iᵀx, y_i) + ν²/2 · xᵀΛx      (``core.objectives``).

Inner loop: every Newton system (AᵀW(x_t)A + ν²Λ) Δ = −∇F(x_t) is a
*weighted* instance of the paper's quadratic (1.1), solved by the batched
padded adaptive engine (``core.adaptive_padded``) with the Hessian weights
W(x_t) riding through ``Quadratic.row_weights`` — the sketch providers
embed W^{1/2}A inside their one streaming pass over A, so each outer
iteration touches A exactly once for its sketch (plus the O(nd) margins /
gradient passes), never materializing a weighted copy.

Warm-started ladder (the adaptive-Newton-sketch idea, arXiv:2105.07291):
the per-problem doubling-ladder level found by outer step t seeds step
t+1's ``init_level`` — the effective dimension of AᵀW(x)A drifts slowly
along the Newton path, so re-climbing the ladder from m=1 each step would
waste the sketch sizes the controller already discovered. The sketch
itself is RE-SAMPLED each step (fold_in(key, t)): weights change, and a
fresh sketch keeps the δ̃ certificates honest.

Stopping is per-problem on the approximate Newton decrement
λ̃²/2 = −⟨∇F, Δ⟩/2 (the exact analogue of the quadratic core's δ̃ = (2.3));
each problem freezes once its decrement clears ``tol`` while the rest of
the batch keeps iterating inside the same fixed-shape executables.

The driver is a bounded host loop (≤ ``newton_iters``) over three jitted
pieces — gradient/weights, the padded engine, line search — all of whose
shapes are step-invariant, so every Newton step after the first reuses
compiled executables (the engine sees ``init_level`` as a traced array).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.precision import fp32_contractions

from .adaptive_padded import _is_single_key, padded_adaptive_solve_batched
from .objectives import (
    GLMObjective,
    get_objective,
    glm_grad_and_weights,
    glm_value,
)
from .quadratic import Quadratic, _as_batched_reg
from .status import SolveStatus


@partial(jax.jit, static_argnames=("obj",))
@fp32_contractions
def _grad_and_weights(obj: GLMObjective, A, y, nu, lam, x):
    return glm_grad_and_weights(obj, A, y, nu, lam, x)


@partial(jax.jit, static_argnames=("obj", "backtracks", "c1"))
@fp32_contractions
def _line_search(obj: GLMObjective, A, y, nu, lam, x, delta, dec, active,
                 *, backtracks: int, c1: float):
    """Per-problem backtracking Armijo: largest s ∈ {1, ½, …, 2^{1−K}} with
    F(x + sΔ) ≤ F(x) − c₁·s·λ̃². Returns (x⁺, s, made_progress); problems
    with no admissible step (or a non-descent Δ) keep x and report False —
    the driver freezes them rather than looping on a dead direction."""
    F0 = glm_value(obj, A, y, nu, lam, x)                     # (B,)
    ss = 0.5 ** jnp.arange(backtracks, dtype=F0.dtype)        # (K,)
    vals = jax.vmap(
        lambda s: glm_value(obj, A, y, nu, lam, x + s * delta))(ss)  # (K, B)
    # approximate Armijo: once the true decrease c₁sλ̃² falls below the
    # floating-point resolution of F itself, an exact comparison would
    # reject every candidate and stall the problem above tolerance — the
    # eps·(1+|F|) slack accepts steps whose descent f32 cannot resolve
    # (Newton's local contraction guarantees they still shrink λ̃²)
    slack = jnp.finfo(F0.dtype).eps * (1.0 + jnp.abs(F0))
    ok = (vals <= F0[None, :] - c1 * ss[:, None] * dec[None, :]
          + slack[None, :]) & jnp.isfinite(vals)
    any_ok = jnp.any(ok, axis=0) & (dec > 0)
    first = jnp.argmax(ok, axis=0)                 # first True (largest s)
    s = jnp.where(any_ok, ss[first], 0.0)
    move = (active & any_ok)[:, None]
    return jnp.where(move, x + s[:, None] * delta, x), s, any_ok


def adaptive_newton_solve_batched(
    family: GLMObjective | str,
    A: jnp.ndarray,
    y: jnp.ndarray,
    nu,
    *,
    lam_diag=None,
    keys: jax.Array | None = None,
    m_max: int,
    method: str = "pcg",
    sketch: str = "gaussian",
    newton_iters: int = 30,
    tol: float = 1e-10,
    inner_max_iters: int = 100,
    inner_tol: float = 1e-10,
    rho: float = 0.5,
    ls_backtracks: int = 12,
    ls_c1: float = 1e-4,
    mesh=None,
    compute_dtype: str = "fp32",
    deadline_s: float | None = None,
):
    """Solve a batch of B regularized GLM problems by adaptive sketched
    Newton. A (B, n, d) per-problem or (n, d) shared; y (B, n); ν scalar or
    (B,); Λ (d,) or (B, d). Returns (x, stats) with x (B, d) and

    * ``newton_iters``  (B,)  accepted outer steps per problem,
    * ``decrement``     (B,)  final λ̃²/2 (the Newton-level certificate),
    * ``converged``     (B,)  decrement ≤ tol (False = stalled/budget),
    * ``m_trajectory``  (T, B) inner m_final after each outer step,
    * ``m_final``       (B,)  last inner sketch size,
    * ``level``         (B,)  final ladder level (warm-start token),
    * ``inner_iters``   (B,)  total inner iterations across all steps.

    ``deadline_s``: wall-clock budget over the whole Newton solve, checked
    between OUTER steps (the natural segment boundary of the host-driven
    loop — the first step always runs). Problems still unfinished when the
    budget runs out keep their current iterate and its honest decrement
    and report ``DEADLINE_EXCEEDED`` (DESIGN.md §11).
    """
    y = jnp.asarray(y)
    if keys is None:
        keys = jax.random.PRNGKey(0)
    if _is_single_key(keys):
        keys = jax.random.split(keys, y.shape[0])

    def inner_solve(t, q_t, level):
        if mesh is not None:
            from .distributed import shard_quadratic

            q_t = shard_quadratic(q_t, mesh)
        step_keys = jax.vmap(lambda k: jax.random.fold_in(k, t))(keys)
        return padded_adaptive_solve_batched(
            q_t, step_keys, m_max=m_max, method=method, sketch=sketch,
            max_iters=inner_max_iters, rho=rho, tol=inner_tol, mesh=mesh,
            init_level=level, compute_dtype=compute_dtype)

    return _newton_loop(family, A, y, nu, lam_diag, inner_solve,
                        newton_iters=newton_iters, tol=tol,
                        ls_backtracks=ls_backtracks, c1=ls_c1,
                        deadline_s=deadline_s)


def _newton_loop(family, A, y, nu, lam_diag, inner_solve, *,
                 newton_iters: int, tol: float, ls_backtracks: int,
                 c1: float = 1e-4, deadline_s: float | None = None):
    """The shared damped-Newton outer loop (driver AND references — one
    copy of the stopping/line-search/freeze logic, so the baselines always
    validate the exact loop the driver runs). ``inner_solve(t, q_t, level)``
    produces the Newton step for the weighted subproblem ``q_t`` and either
    the padded engine's stats dict (driver) or None (references)."""
    obj = get_objective(family)
    A = jnp.asarray(A)
    y = jnp.asarray(y)
    B = y.shape[0]
    d = A.shape[-1]
    nu_b, lam_b = _as_batched_reg(nu, lam_diag, B, d, A.dtype)

    x = jnp.zeros((B, d), A.dtype)
    level = jnp.zeros((B,), jnp.int32)
    done = jnp.zeros((B,), bool)
    dec = jnp.full((B,), jnp.inf, A.dtype)
    iters = jnp.zeros((B,), jnp.int32)
    inner_total = jnp.zeros((B,), jnp.int32)
    inner_status = jnp.zeros((B,), jnp.int32)   # last active inner verdict
    m_traj = []
    expired = jnp.zeros((B,), bool)
    t_start = time.perf_counter()

    for t in range(newton_iters):
        if (deadline_s is not None and t > 0
                and time.perf_counter() - t_start >= deadline_s):
            # budget spent between outer steps: unfinished problems keep
            # their current iterate + honest decrement, verdict below
            expired = ~done
            break
        g, w = _grad_and_weights(obj, A, y, nu_b, lam_b, x)
        q_t = Quadratic(A=A, b=-g, nu=nu_b, lam_diag=lam_b, batched=True,
                        row_weights=w)
        delta, s_in = inner_solve(t, q_t, level)
        # λ̃² = −⟨∇F, Δ⟩ (Δ solves the weighted system ≈ −H⁻¹∇F)
        dec_t = -jnp.sum(g * delta, axis=-1)
        newly_done = 0.5 * dec_t <= tol
        active = ~done & ~newly_done
        x, _, progressed = _line_search(
            obj, A, y, nu_b, lam_b, x, delta, dec_t, active,
            backtracks=ls_backtracks, c1=c1)
        if s_in is not None:
            # carry the discovered ladder level across steps (warm m_t)
            level = jnp.where(~done, s_in["level"], level)
            inner_total = inner_total + jnp.where(~done, s_in["iters"], 0)
            if "status" in s_in:
                inner_status = jnp.where(~done, s_in["status"], inner_status)
            m_traj.append(np.asarray(jnp.where(~done, s_in["m_final"], 0)))
        dec = jnp.where(~done, 0.5 * dec_t, dec)
        iters = iters + active.astype(jnp.int32)
        done = done | newly_done | (active & ~progressed)
        if bool(jnp.all(done)):
            break

    m_traj_arr = np.stack(m_traj) if m_traj else np.zeros((0, B), np.int32)
    m_last = np.zeros((B,), np.int32)
    for row in m_traj_arr:                     # last non-frozen m per problem
        m_last = np.where(row > 0, row, m_last)
    converged = dec <= tol
    # GLM verdict (DESIGN.md §9): convergence of the *outer* decrement is
    # what certifies the answer; a non-converged problem inherits its last
    # active inner engine failure (a poisoned/unusable Newton system is the
    # cause), and otherwise stalled — frozen by the line search or the
    # outer budget.
    engine_fail = (inner_status == jnp.int32(SolveStatus.LEVEL_INVALID)) | (
        inner_status == jnp.int32(SolveStatus.NAN_POISONED))
    status = jnp.where(
        converged, jnp.int32(SolveStatus.OK),
        jnp.where(expired, jnp.int32(SolveStatus.DEADLINE_EXCEEDED),
                  jnp.where(engine_fail, inner_status,
                            jnp.int32(SolveStatus.STALLED))))
    stats = {
        "newton_iters": iters,
        "decrement": dec,
        "converged": converged,
        "m_trajectory": m_traj_arr,
        "m_final": jnp.asarray(m_last),
        "level": level,
        "inner_iters": inner_total,
        "status": status,
        "stalled": status == jnp.int32(SolveStatus.STALLED),
    }
    return x, stats


def adaptive_newton_solve(family, A, y, nu, *, key=None, **kw):
    """Single-problem convenience: a B=1 batch through the batched driver;
    stats come back as scalars."""
    A = jnp.asarray(A)
    y = jnp.asarray(y)
    keys = None if key is None else (
        key[None] if _is_single_key(key) else key)
    x, stats = adaptive_newton_solve_batched(
        family, A, y[None, :], nu, keys=keys, **kw)
    out = {}
    for k, v in stats.items():
        if k == "m_trajectory":
            out[k] = v[:, 0]
        else:
            out[k] = v[0] if getattr(v, "ndim", 0) else v
    return x[0], out


def newton_cg_reference(family, A, y, nu, *, lam_diag=None,
                        newton_iters: int = 30, cg_iters: int = 200,
                        tol: float = 1e-10, ls_backtracks: int = 12):
    """Unpreconditioned Newton-CG baseline (benchmarks): the SAME outer
    loop, inner systems solved by plain CG on the weighted quadratic —
    what the GLM path costs WITHOUT sketched preconditioning."""
    from .solvers import cg_solve

    def inner_solve(t, q_t, level):
        delta, _ = cg_solve(q_t, jnp.zeros_like(q_t.b), iters=cg_iters)
        return delta, None

    x, _ = _newton_loop(family, A, y, nu, lam_diag, inner_solve,
                        newton_iters=newton_iters, tol=tol,
                        ls_backtracks=ls_backtracks)
    return x


def irls_reference(family, A, y, nu, *, lam_diag=None,
                   newton_iters: int = 50, tol: float = 1e-12):
    """Exact-Newton / IRLS reference (tests): the SAME outer loop, dense
    factorizations of the weighted Hessian via ``direct_solve``."""
    from .quadratic import direct_solve

    def inner_solve(t, q_t, level):
        return direct_solve(q_t), None

    x, _ = _newton_loop(family, A, y, nu, lam_diag, inner_solve,
                        newton_iters=newton_iters, tol=tol,
                        ls_backtracks=20)
    return x
