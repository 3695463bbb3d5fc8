"""Factorizations of the sketched Hessian H_S = (SA)ᵀ(SA) + ν²Λ (paper §4.1.1).

Two regimes, chosen exactly as in the paper:

* m ≥ d  (primal): form H_S ∈ R^{d×d}, Cholesky in O(d³); solves O(d²).
* m < d  (dual / Woodbury): form W_S = SAΛ⁻¹(SA)ᵀ + ν²I_m ∈ R^{m×m},
  Cholesky in O(m³); solves O(md) via
      v = Λ⁻¹/ν² · (I_d − (SA)ᵀ W_S⁻¹ SA Λ⁻¹) z .

Batch polymorphism (DESIGN.md §6): ``factorize`` accepts SA with a leading
problem axis (B, m, d) — the factorization and ``solve`` batch over it —
and ``factorize_shared`` covers the shared-sketch λ-batch, where one SA is
factorized against B different (ν, Λ) regularizers with the Gram matrix
(SAᵀSA, resp. SAΛ⁻¹SAᵀ) formed once.

``shifted_ladder_inverses`` generalizes the same shift-at-factorization
idea to the adaptive engine's doubling ladder (DESIGN.md §13): the
(L, B, d, d) level Grams (SA)ᵀ(SA) are λ-free — ν²Λ enters only here, as a
diagonal shift added immediately before the flattened batched Cholesky —
so ONE one-touch sketch pass serves every λ point of a regularization
path; only this O(L·B·d³) factorization is repeated per λ.
``shifted_ladder_dual`` is the same ladder in the dual regime above, for
ladders whose levels all have m < d and are prefixes of one row stream:
an m×m factorization per level, O(B·m_max²·d + Σ m³) in all.

The factorization object is a pytree so it can be closed over / donated in
jitted solver loops.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, solve_triangular


def _chol_solve(chol: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Lower-Cholesky solve; batches over leading axes."""
    y = solve_triangular(chol, z, lower=True)
    return solve_triangular(jnp.swapaxes(chol, -1, -2), y, lower=False)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SketchedPrecond:
    """Cached factorization of H_S; solves  H_S v = z  in O(min(m,d)·d)."""

    mode: str               # "primal" | "dual"
    chol: jnp.ndarray       # (d,d) or (m,m) lower Cholesky; (B,·,·) batched
    SA: jnp.ndarray | None  # (m,d) or (B,m,d), kept only in dual mode
    nu2: jnp.ndarray        # scalar ν²; (B,) batched
    lam_diag: jnp.ndarray   # (d,) diagonal of Λ; (B,d) batched
    batched: bool = False   # static: leading problem axis on chol/ν²/Λ

    def tree_flatten(self):
        return (self.chol, self.SA, self.nu2, self.lam_diag), (
            self.mode, self.batched)

    @classmethod
    def tree_unflatten(cls, aux, children):
        chol, SA, nu2, lam = children
        return cls(mode=aux[0], chol=chol, SA=SA, nu2=nu2, lam_diag=lam,
                   batched=aux[1])

    def solve(self, z: jnp.ndarray) -> jnp.ndarray:
        """Solve H_S v = z. Supports vector (d,) or matrix (d,c) RHS; with
        ``batched`` z carries the problem axis: (B, d)."""
        if self.batched:
            return self._solve_batched(z)
        squeeze = z.ndim == 1
        if squeeze:
            z = z[:, None]
        if self.mode == "primal":
            v = _chol_solve(self.chol, z)
        else:
            SA, nu2 = self.SA, self.nu2
            lam_inv = 1.0 / self.lam_diag
            zi = lam_inv[:, None] * z                      # Λ⁻¹ z
            w = _chol_solve(self.chol, SA @ zi)            # W_S⁻¹ SA Λ⁻¹ z
            v = (zi - lam_inv[:, None] * (SA.T @ w)) / nu2
        return v[:, 0] if squeeze else v

    def _solve_batched(self, z: jnp.ndarray) -> jnp.ndarray:
        if self.mode == "primal":
            return _chol_solve(self.chol, z[..., None])[..., 0]
        SA = self.SA
        lam_inv = 1.0 / self.lam_diag                      # (B, d)
        zi = lam_inv * z                                   # Λ⁻¹ z, (B, d)
        if SA.ndim == 2:                                   # shared sketch
            SAzi = jnp.einsum("md,bd->bm", SA, zi)
            w = _chol_solve(self.chol, SAzi[..., None])[..., 0]
            back = jnp.einsum("md,bm->bd", SA, w)
        else:
            SAzi = jnp.einsum("bmd,bd->bm", SA, zi)
            w = _chol_solve(self.chol, SAzi[..., None])[..., 0]
            back = jnp.einsum("bmd,bm->bd", SA, w)
        return (zi - lam_inv * back) / self.nu2[:, None]


def _diag_embed(x: jnp.ndarray) -> jnp.ndarray:
    return jax.vmap(jnp.diag)(x)


def factorize(
    SA: jnp.ndarray,
    nu: float | jnp.ndarray,
    lam_diag: jnp.ndarray,
    *,
    jitter: float = 0.0,
) -> SketchedPrecond:
    """Factorize H_S given the sketched matrix SA ∈ R^{m×d}, or a batch of
    sketched matrices SA ∈ R^{B×m×d} (ν, Λ broadcast or per-problem)."""
    if SA.ndim == 3:
        return _factorize_batched(SA, nu, lam_diag, jitter=jitter)
    m, d = SA.shape
    nu2 = jnp.asarray(nu, SA.dtype) ** 2
    if m >= d:
        H_S = SA.T @ SA + jnp.diag(nu2 * lam_diag)
        if jitter:
            H_S = H_S + jitter * jnp.eye(d, dtype=SA.dtype)
        chol, _ = cho_factor(H_S, lower=True)
        return SketchedPrecond(
            mode="primal", chol=chol, SA=None, nu2=nu2, lam_diag=lam_diag
        )
    lam_inv = 1.0 / lam_diag
    W_S = (SA * lam_inv[None, :]) @ SA.T + nu2 * jnp.eye(m, dtype=SA.dtype)
    if jitter:
        W_S = W_S + jitter * jnp.eye(m, dtype=SA.dtype)
    chol, _ = cho_factor(W_S, lower=True)
    return SketchedPrecond(
        mode="dual", chol=chol, SA=SA, nu2=nu2, lam_diag=lam_diag
    )


def _factorize_batched(SA, nu, lam_diag, *, jitter: float = 0.0
                       ) -> SketchedPrecond:
    B, m, d = SA.shape
    nu2 = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(nu, SA.dtype)) ** 2, (B,))
    lam_diag = jnp.broadcast_to(jnp.asarray(lam_diag, SA.dtype), (B, d))
    if m >= d:
        H_S = jnp.einsum("bmd,bme->bde", SA, SA) + _diag_embed(
            nu2[:, None] * lam_diag)
        if jitter:
            H_S = H_S + jitter * jnp.eye(d, dtype=SA.dtype)
        chol = jnp.linalg.cholesky(H_S)
        return SketchedPrecond(mode="primal", chol=chol, SA=None, nu2=nu2,
                               lam_diag=lam_diag, batched=True)
    lam_inv = 1.0 / lam_diag
    W_S = jnp.einsum("bmd,bnd->bmn", SA * lam_inv[:, None, :], SA) + (
        nu2[:, None, None] * jnp.eye(m, dtype=SA.dtype))
    if jitter:
        W_S = W_S + jitter * jnp.eye(m, dtype=SA.dtype)
    chol = jnp.linalg.cholesky(W_S)
    return SketchedPrecond(mode="dual", chol=chol, SA=SA, nu2=nu2,
                           lam_diag=lam_diag, batched=True)


def factorize_shared(
    SA: jnp.ndarray,
    nu: jnp.ndarray,
    lam_diag: jnp.ndarray,
    *,
    jitter: float = 0.0,
) -> SketchedPrecond:
    """λ-batch fast path: ONE sketched matrix SA (m, d) factorized against a
    batch of regularizers ν (B,), Λ (B, d) — e.g. a regularization path or
    per-tenant λ heads over shared data.

    The O(md²) Gram product SAᵀSA (primal) is computed once; only the B
    diagonal additions and Cholesky factorizations are batched. In the dual
    (m < d) regime the Λ-weighted Gram SAΛ⁻¹SAᵀ is shared only when Λ is
    shared across the batch; per-problem Λ falls back to a batched Gram."""
    m, d = SA.shape
    nu2 = jnp.atleast_1d(jnp.asarray(nu, SA.dtype)) ** 2
    B = nu2.shape[0]
    lam_shared = jnp.asarray(lam_diag, SA.dtype).ndim == 1
    lam_diag = jnp.broadcast_to(jnp.asarray(lam_diag, SA.dtype), (B, d))
    if m >= d:
        G = SA.T @ SA                                        # once, shared
        H_S = G[None, :, :] + _diag_embed(nu2[:, None] * lam_diag)
        if jitter:
            H_S = H_S + jitter * jnp.eye(d, dtype=SA.dtype)
        chol = jnp.linalg.cholesky(H_S)
        return SketchedPrecond(mode="primal", chol=chol, SA=None, nu2=nu2,
                               lam_diag=lam_diag, batched=True)
    if lam_shared:
        K = (SA * (1.0 / lam_diag[0])[None, :]) @ SA.T       # once, shared
        W_S = K[None, :, :] + nu2[:, None, None] * jnp.eye(m, dtype=SA.dtype)
    else:
        W_S = jnp.einsum("md,bd,nd->bmn", SA, 1.0 / lam_diag, SA) + (
            nu2[:, None, None] * jnp.eye(m, dtype=SA.dtype))
    if jitter:
        W_S = W_S + jitter * jnp.eye(m, dtype=SA.dtype)
    chol = jnp.linalg.cholesky(W_S)
    return SketchedPrecond(mode="dual", chol=chol, SA=SA, nu2=nu2,
                           lam_diag=lam_diag, batched=True)


def shifted_ladder_inverses(
    grams: jnp.ndarray,
    nu: jnp.ndarray,
    lam_diag: jnp.ndarray,
) -> jnp.ndarray:
    """Per-λ shifted factorization of a λ-FREE ladder of level Grams.

    ``grams`` is the (L, B, d, d) stack of unshifted sketched Grams
    (SA)ᵀ(SA) at every doubling-ladder level — the output of one one-touch
    sketch pass, independent of the regularizer. The ν²Λ shift is applied
    HERE, so a regularization path factorizes the same ladder once per λ
    point (O(L·B·d³) each) while paying the O(B·m_max·n·d) sketch pass
    exactly once for the whole grid (DESIGN.md §13).

    Returns the (L, B, d, d) explicit inverses (G_l + ν²Λ)⁻¹ via one
    flattened batched Cholesky + two triangular solves — with the inverses
    precomputed, a doubling inside the solve loop is a pure gather and the
    per-iteration preconditioner application one fused batched matvec.
    The forward error of an explicit inverse is the same O(ε·κ) as
    triangular solves, which a *preconditioner* tolerates."""
    L, B, d, _ = grams.shape
    reg = (nu**2)[:, None] * lam_diag                        # (B, d)
    HS = grams + jax.vmap(jnp.diag)(reg)[None, :, :, :]
    HS = HS.reshape(L * B, d, d)
    chol = jnp.linalg.cholesky(HS)
    eye = jnp.broadcast_to(jnp.eye(d, dtype=HS.dtype), HS.shape)
    y = solve_triangular(chol, eye, lower=True)
    pinv = solve_triangular(jnp.swapaxes(chol, -1, -2), y, lower=False)
    return pinv.reshape(L, B, d, d)


def shifted_ladder_dual(
    rows: jnp.ndarray,
    ladder: tuple[int, ...],
    nu: jnp.ndarray,
    lam_diag: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The dual (Woodbury) form of ``shifted_ladder_inverses``, for a ladder
    whose levels are prefixes of one row stream and all have m < d.

    ``rows`` is the (B, m_max, d) stream R whose level-m Gram is
    R_mᵀR_m/m (R_m: its first m rows). With D = ν²Λ, s = D^{-1/2} and
    V_m = R_m·diag(s),

        (R_mᵀR_m/m + D)⁻¹ = diag(s)·(I − U_mᵀU_m)·diag(s),
        U_m = L_m⁻¹V_m,   L_mL_mᵀ = m·I + V_mV_mᵀ,

    so each level factors an m×m matrix, at its own size: K = V Vᵀ is
    formed once at HIGHEST and level m Choleskys m·I + K[:m, :m]. U_m has
    spectral norm below 1 and comes from a backward-stable triangular
    solve, which keeps the fp32 error of applying I − UᵀU to O(ε‖y‖): the
    order of the primal explicit inverse's. (Applying (m·I + K)⁻¹ between
    V and Vᵀ instead loses O(ε·κ²) in the top directions.) Returns
    ``(U, s)``: the (L, B, m_max, d) table of the U_m, zero-padded below
    row m, and s as (B, d)."""
    B, m_max, _ = rows.shape
    hi = jax.lax.Precision.HIGHEST
    s = jax.lax.rsqrt((nu**2)[:, None] * lam_diag)              # (B, d)
    V = rows * s[:, None, :]
    K = jnp.einsum("bmd,bnd->bmn", V, V, precision=hi)
    tables = []
    for m in ladder:
        chol = jnp.linalg.cholesky(K[:, :m, :m] + m * jnp.eye(m, dtype=K.dtype))
        U = solve_triangular(chol, V[:, :m], lower=True)
        tables.append(jnp.pad(U, ((0, 0), (0, m_max - m), (0, 0))))
    return jnp.stack(tables), s


def factorization_cost_flops(m: int, n: int, d: int) -> float:
    """Flops to form + factorize H_S (paper §4.1.1), excluding the sketch."""
    if m >= d:
        return 2.0 * m * d * d + d**3 / 3.0
    return 2.0 * m * m * d + m**3 / 3.0
