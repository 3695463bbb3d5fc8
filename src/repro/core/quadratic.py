"""Problem container for  min_x ½⟨x, Hx⟩ − bᵀx,  H = AᵀW A + ν²Λ  (paper (1.1)).

``Quadratic`` is matrix-free: it exposes Hv, ∇f, f, and the sketch of A.
It supports matrix right-hand sides B ∈ R^{d×c} (multi-class heads — the
paper's experiments use one-hot label matrices).

Row weights (DESIGN.md §8): an optional ``row_weights`` w ≥ 0 turns the
Gram into AᵀWA with W = diag(w) — the Hessian of every regularized GLM's
Newton subproblem (AᵀW(x)A + ν²Λ) Δ = −∇F. The container stays matrix-free
about it: ``hvp`` computes Aᵀ(w ⊙ (Av)) so the weighted matrix W^{1/2}A is
NEVER materialized; the sketch providers (``core.level_grams``) fuse w^{1/2}
into their one streaming pass over A the same way. w is (n,) for single
problems and (B, n) — per problem, even with shared A — when batched.

Batch polymorphism (DESIGN.md §6): every op also accepts a *leading problem
axis*. A batched ``Quadratic`` (``batched=True``) holds B independent
problems and comes in two layouts:

* per-problem data:  A (B, n, d), b (B, d), ν (B,), Λ (B, d);
* shared-A λ-batch:  A (n, d) shared, b (B, d), ν (B,), Λ (B, d) — the
  layout of hyperparameter sweeps / per-tenant heads over one dataset,
  where the Gram matrix AᵀA is computed ONCE and reused across the batch.

``batched`` is static pytree metadata, so jitted solvers specialize on it
without retracing per batch size. Scalar reductions (value, error, δ̃)
return a (B,) vector in batched mode.

A distributed (row-sharded) variant lives in ``repro.core.distributed``; this
module is the single-device semantics both share.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.precision import fp32_contractions


def pdot(a: jnp.ndarray, b: jnp.ndarray, batched: bool) -> jnp.ndarray:
    """⟨a, b⟩ summed over all axes — except the leading problem axis when
    ``batched`` (returns (B,))."""
    if batched:
        return jnp.sum(a * b, axis=tuple(range(1, a.ndim)))
    return jnp.sum(a * b)


def pscale(c: jnp.ndarray, batched: bool) -> jnp.ndarray:
    """Broadcast a per-problem scalar (B,) against (B, d) state arrays."""
    return c[..., None] if batched else c


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Quadratic:
    A: jnp.ndarray          # (n, d) data matrix; (B, n, d) or shared (n, d)
    b: jnp.ndarray          # (d,) or (d, c); (B, d) when batched
    nu: jnp.ndarray         # scalar regularization ν; (B,) when batched
    lam_diag: jnp.ndarray   # (d,) diagonal of Λ ⪰ I; (B, d) when batched
    batched: bool = False   # static: leading problem axis on b/ν/Λ (and A
                            # unless shared)
    row_weights: jnp.ndarray | None = None  # W = diag(w): (n,); (B, n) when
                            # batched (per problem even with shared A)

    def tree_flatten(self):
        return (self.A, self.b, self.nu, self.lam_diag,
                self.row_weights), (self.batched,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:4], batched=aux[0], row_weights=children[4])

    # -- dimensions --------------------------------------------------------
    @property
    def shared_A(self) -> bool:
        return self.batched and self.A.ndim == 2

    @property
    def n(self) -> int:
        return self.A.shape[-2]

    @property
    def d(self) -> int:
        return self.A.shape[-1]

    @property
    def batch(self) -> int:
        if not self.batched:
            raise ValueError("not a batched problem")
        return self.b.shape[0]

    # -- operator ----------------------------------------------------------
    def _reg(self, v: jnp.ndarray) -> jnp.ndarray:
        """ν²Λ v with the layout-appropriate broadcast."""
        if self.batched:
            return (self.nu**2)[:, None] * self.lam_diag * v
        lam = self.lam_diag
        if v.ndim == 1:
            return (self.nu**2) * lam * v
        return (self.nu**2) * lam[:, None] * v

    def hvp(self, v: jnp.ndarray) -> jnp.ndarray:
        """H v = AᵀWA v + ν²Λ v  in O(nd) per problem (never forms H or
        W^{1/2}A: the weight lands on the (·, n) intermediate Av)."""
        w = self.row_weights
        if self.batched:
            if self.shared_A:
                Av = v @ self.A.T                      # (B, n)
                if w is not None:
                    Av = w * Av
                AtAv = Av @ self.A                     # (B, d)
            else:
                Av = jnp.einsum("bnd,bd->bn", self.A, v)
                if w is not None:
                    Av = w * Av
                AtAv = jnp.einsum("bnd,bn->bd", self.A, Av)
            return AtAv + self._reg(v)
        Av = self.A @ v
        if w is not None:
            Av = (w[:, None] if Av.ndim == 2 else w) * Av
        return self.A.T @ Av + self._reg(v)

    def grad(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.hvp(x) - self.b

    def value(self, x: jnp.ndarray) -> jnp.ndarray:
        return 0.5 * pdot(x, self.hvp(x), self.batched) - pdot(
            self.b, x, self.batched
        )

    def error(self, x: jnp.ndarray, x_star: jnp.ndarray) -> jnp.ndarray:
        """δ_x = ½‖x − x*‖²_H (summed over columns for matrix RHS; per
        problem for batched)."""
        dx = x - x_star
        return 0.5 * pdot(dx, self.hvp(dx), self.batched)

    # -- batch utilities ---------------------------------------------------
    def problem(self, i: int) -> "Quadratic":
        """Extract problem i of a batched Quadratic as a single problem."""
        if not self.batched:
            raise ValueError("not a batched problem")
        A = self.A if self.shared_A else self.A[i]
        w = None if self.row_weights is None else self.row_weights[i]
        return Quadratic(A=A, b=self.b[i], nu=self.nu[i],
                         lam_diag=self.lam_diag[i], row_weights=w)

    def with_row_weights(self, w: jnp.ndarray | None) -> "Quadratic":
        """Same problem under the weighted Gram AᵀWA (W = diag(w)).

        ``w`` is (n,) single / (B, n) batched — per problem even when A is
        shared, which is the Newton-subproblem layout (weights depend on
        the iterate)."""
        if w is not None:
            w = jnp.asarray(w, self.A.dtype)
            want = (self.batch, self.n) if self.batched else (self.n,)
            if w.shape != want:
                raise ValueError(
                    f"row_weights shape {w.shape} != expected {want}")
        return dataclasses.replace(self, row_weights=w)


def _as_batched_reg(nu, lam_diag, B: int, d: int, dtype):
    """Materialize ν as (B,) and Λ as (B, d) so batched ops are uniform."""
    nu = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(nu, dtype)), (B,))
    if lam_diag is None:
        lam_diag = jnp.ones((d,), dtype)
    lam_diag = jnp.broadcast_to(jnp.asarray(lam_diag, dtype), (B, d))
    return nu, lam_diag


@fp32_contractions
def from_least_squares(A, y, nu, lam_diag=None) -> Quadratic:
    """Ridge regression  min ½‖Ax − y‖² + ν²/2 ‖Λ^{1/2}x‖²  as (1.1)."""
    A = jnp.asarray(A)
    y = jnp.asarray(y)
    if lam_diag is None:
        lam_diag = jnp.ones((A.shape[1],), A.dtype)
    return Quadratic(A=A, b=A.T @ y, nu=jnp.asarray(nu, A.dtype), lam_diag=lam_diag)


@fp32_contractions
def from_least_squares_batch(A, Y, nu, lam_diag=None) -> Quadratic:
    """Batched ridge:  A (B, n, d) per-problem or (n, d) shared; Y (B, n);
    ν scalar or (B,); Λ (d,) or (B, d)."""
    A = jnp.asarray(A)
    Y = jnp.asarray(Y)
    B, d = Y.shape[0], A.shape[-1]
    if A.ndim == 2:
        b = Y @ A                                   # (B, d), shared Gram path
    else:
        b = jnp.einsum("bnd,bn->bd", A, Y)
    nu, lam_diag = _as_batched_reg(nu, lam_diag, B, d, A.dtype)
    return Quadratic(A=A, b=b, nu=nu, lam_diag=lam_diag, batched=True)


@fp32_contractions
def lambda_sweep(A, y, nus, lam_diag=None) -> Quadratic:
    """Shared-A regularization-path batch: one (A, y), B values of ν.

    The returned problem has A shared, so Gram-forming consumers
    (``direct_solve``, ``precond.factorize_shared``) pay the O(nd²) once."""
    A = jnp.asarray(A)
    y = jnp.asarray(y)
    nus = jnp.asarray(nus, A.dtype)
    b1 = A.T @ y
    b = jnp.broadcast_to(b1[None, :], (nus.shape[0], A.shape[1]))
    nu, lam_diag = _as_batched_reg(nus, lam_diag, nus.shape[0], A.shape[1],
                                   A.dtype)
    return Quadratic(A=A, b=b, nu=nu, lam_diag=lam_diag, batched=True)


def stack_quadratics(qs: list[Quadratic]) -> Quadratic:
    """Stack same-shape single problems along a new leading problem axis.
    Row weights stack too (all problems weighted or none — a mix has no
    faithful batched representation and must not silently drop weights)."""
    if any(q.batched for q in qs):
        raise ValueError("stack_quadratics takes single problems")
    n_weighted = sum(q.row_weights is not None for q in qs)
    if n_weighted not in (0, len(qs)):
        raise ValueError(
            f"cannot stack {n_weighted} weighted with "
            f"{len(qs) - n_weighted} unweighted problems")
    A = jnp.stack([q.A for q in qs])
    b = jnp.stack([q.b for q in qs])
    nu = jnp.stack([jnp.asarray(q.nu) for q in qs])
    lam = jnp.stack([q.lam_diag for q in qs])
    w = (jnp.stack([q.row_weights for q in qs]) if n_weighted else None)
    return Quadratic(A=A, b=b, nu=nu, lam_diag=lam, batched=True,
                     row_weights=w)


def gram(A: jnp.ndarray, w: jnp.ndarray | None = None, *,
         chunk: int = 1024) -> jnp.ndarray:
    """AᵀA — or AᵀWA with per-problem row weights w (B, n) — as a
    ``lax.scan`` over n-chunks with Kahan-compensated accumulation.

    A is (B, n, d) per-problem or (n, d) shared; the result is (B, d, d),
    or (d, d) for a shared, unweighted A. The only weighted intermediate is
    the (B, chunk, d) tile — never an (n, d)-sized weighted copy of A (the
    streaming guarantee the engine's weighted ``gram_hvp`` relies on).

    Why chunks and compensation: the certificates solve against this Gram,
    and one fp32 dot over all n rows accumulates its rounding error along
    n. On a TPU v5e at n = 2¹⁷ that error is 1.3e-5 relative even at
    HIGHEST precision, which a condition number of 10³ turns into 1e-3 in
    x. Per-chunk Grams summed with Kahan compensation keep the error near
    fp32 rounding of the result, independent of n."""
    shared = A.ndim == 2
    n, d = A.shape[-2], A.shape[-1]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        # zero rows add exact zeros to the Gram
        A = jnp.pad(A, ((0, pad), (0, 0)) if shared
                    else ((0, 0), (0, pad), (0, 0)))
        if w is not None:
            w = jnp.pad(w, ((0, 0), (0, pad)))
    hi = jax.lax.Precision.HIGHEST

    def step(carry, c_idx):
        total, comp = carry
        r0 = c_idx * chunk
        a_c = jax.lax.dynamic_slice_in_dim(A, r0, chunk, axis=A.ndim - 2)
        if w is None:
            g = (jnp.matmul(a_c.T, a_c, precision=hi) if shared
                 else jnp.einsum("bcd,bce->bde", a_c, a_c, precision=hi))
        else:
            w_c = jax.lax.dynamic_slice_in_dim(w, r0, chunk, axis=1)
            g = jnp.einsum("bc,cd,ce->bde" if shared else "bc,bcd,bce->bde",
                           w_c, a_c, a_c, precision=hi)
        g = g - comp
        new = total + g
        return (new, (new - total) - g), None

    shape = ((d, d) if shared and w is None
             else ((A.shape[0] if w is None else w.shape[0]), d, d))
    zero = jnp.zeros(shape, A.dtype)
    (total, _), _ = jax.lax.scan(step, (zero, zero),
                                 jnp.arange((n + pad) // chunk))
    return total


@fp32_contractions
def direct_solve(q: Quadratic) -> jnp.ndarray:
    """Baseline: dense Cholesky factor-and-solve, O(nd²+d³) (paper baseline).

    Batched problems get a batched Cholesky; with shared A the Gram matrix
    is formed once and only the ν²Λ diagonal varies across the batch.
    Weighted problems form AᵀWA (this is the dense oracle — materializing
    the weighted matrix is fine here)."""
    w = q.row_weights
    if q.batched:
        from .precond import _chol_solve

        if q.shared_A and w is None:
            G = q.A.T @ q.A                                    # (d, d) once
            H = G[None, :, :] + jax.vmap(jnp.diag)((q.nu**2)[:, None]
                                                   * q.lam_diag)
        else:
            if q.shared_A:                   # per-problem W breaks sharing
                G = jnp.einsum("bn,nd,ne->bde", w, q.A, q.A)
            elif w is None:
                G = jnp.einsum("bnd,bne->bde", q.A, q.A)
            else:
                G = jnp.einsum("bn,bnd,bne->bde", w, q.A, q.A)
            H = G + jax.vmap(jnp.diag)((q.nu**2)[:, None] * q.lam_diag)
        chol = jnp.linalg.cholesky(H)
        return _chol_solve(chol, q.b[..., None])[..., 0]
    Aw = q.A if w is None else q.A * w[:, None]
    H = Aw.T @ q.A + jnp.diag((q.nu**2) * q.lam_diag)
    chol, _ = jax.scipy.linalg.cho_factor(H, lower=True)
    return jax.scipy.linalg.cho_solve((chol, True), q.b)
