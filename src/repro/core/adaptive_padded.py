"""Beyond-paper: fully-jitted adaptive solver with a *padded* sketch.

The paper's Algorithm 4.1 changes the sketch shape at runtime (m doubles),
which forces either recompilation per size or host orchestration
(``core.adaptive``). In serving/TPU environments with fixed-shape
executables, we instead:

* allocate the sketch at a maximum size m_max once;
* keep an *active-row count* m_t as a traced integer; rows ≥ m_t are masked
  to zero and the live rows are rescaled so the masked sketch has exactly
  the law of an m_t-row sketch;
* run the whole adaptive loop as one ``lax.while_loop`` — the improvement
  test, doubling (m_t ← 2·m_t, i.e. unmask more rows) and refactorization
  are all inside the compiled graph.

Multi-problem engine (DESIGN.md §6): the loop is *batch-polymorphic*. A
batched ``Quadratic`` (B problems, per-problem A or shared A) is solved by
ONE while_loop in which m_t, the restart clock t_rel, δ̃_I and the
convergence flag are all per-problem (B,) vectors — each problem follows
its own doubling schedule (driven by its own effective dimension, per
arXiv:2006.05874) inside a single executable. Refactorization is batched:
whenever any problem rejects, the masked factorization is recomputed for
the whole batch at the updated per-problem sizes (unchanged problems
reproduce their factor bit-for-bit, so this is a no-op for them).

Sketch families are pluggable ``LevelGramProvider``s (``core.level_grams``,
DESIGN.md §6): ``gaussian`` (streamed — rows generated on the fly inside
the fused sketch→Gram kernel, masking = prefix of the i.i.d. row stream),
``gaussian_dense`` (the materialized-S memory baseline, same entries),
``sjlt`` (fixed (u, sign) stream; the level-m target ⌊u_i·m⌋ is uniform for
every m and pow2 levels fold pairwise from ONE dispatch), and ``srht``
(one sign flip + one FWHT pass; level-m = the first m rows of a fixed
uniform row-sample stream).

Methods: ``ihs`` (Thm 3.2 thresholds: φ(ρ)=ρ, α=1), ``pcg``
(Alg 4.2 thresholds: φ(ρ)=(1−√(1−ρ))/(1+√(1−ρ)), α=4) and ``polyak``
(heavy-ball, Appendix A — same thresholds as PCG, with the momentum
anchor x_prev reset on every doubling); the method restarts at the
current iterate on every doubling, as in Algorithm 4.1.

Weighted problems (``q.row_weights``) and warm-started ladders
(``init_level``) serve the GLM Newton driver (``core.newton``,
DESIGN.md §8): the sketch pass embeds W^{1/2}A in the same one touch of A
and the doubling ladder resumes where the previous Newton step left it.

Cost model: m_t only ever visits the doubling ladder {1, 2, 4, …, m_max},
so the preconditioner of every ladder level is PRECOMPUTED before the loop
starts, from one pass of the family's provider touching A exactly ONCE —
matching the paper's O(sketch) + Σ O(factorize) accounting. The sketch
pass *streams* A: the Gaussian family fuses row generation with the A
contraction (``kernels.gaussian_gram`` on TPU, a chunked ``lax.scan``
elsewhere) so S never exists in HBM; the SJLT routes one dispatch through
the Pallas MXU kernel and folds the ladder down; the SRHT pays one FWHT.
Precompute live memory is O(B·m_max·d) row streams + O(B·d²·L) level Grams
in the primal form, O(B·m_max·d·L) in the dual form — never O(B·m_max·n). The in-loop refactorization is only a (B,) gather of
precomputed per-level tables. There is exactly ONE executable and no host
round-trips — the right trade on real TPU pods where launch latency and
recompiles dominate at small m.

Two forms of the ladder (DESIGN.md §6), picked at trace time from what the
call shows:

* **dual** — every level has m < d (m_max < d), the family's levels are
  prefixes of one row stream R (``level_rows``: ``gaussian``,
  ``gaussian_dense``, ``srht``), and the call has no ``mesh`` and no
  ``grams=``. With D = ν²Λ and s = D^{-1/2}, level l factors the m_l×m_l
  m_l·I + (R_l·s)(R_l·s)ᵀ = L_lL_lᵀ and keeps U_l = L_l⁻¹(R_l·s), m_l×d
  (``precond.shifted_ladder_dual``); the loop applies
  H_S⁻¹z = s ⊙ (y − U_lᵀU_l y), y = s ⊙ z. No d×d matrix is formed.
* **primal** — every other call: the (L, B, d, d) level Grams and their
  explicit inverses (``precond.shifted_ladder_inverses``; ν²Λ ≻ 0 keeps
  H_S SPD below d). The sharded pass psums d×d Grams, the ``sjlt``'s
  levels are folds rather than prefixes, and path mode hands in Grams.

The stats entry ``ladder_dual`` says which form ran.

Sharding: ``mesh=`` row-shards A over the mesh's data axes and swaps ONLY
the precompute for the sharded one-touch pass (each shard runs its
family's ladder pass on its rows with independent per-shard randomness;
ONE psum of the (L, B, d, d) level Grams — ``distributed.shard_level_grams``,
DESIGN.md §5); the while_loop and all of the above are unchanged.

Segmentation (DESIGN.md §11): the solve decomposes into four reusable,
individually-jitted pieces — ``prepare_padded_solve`` (one-touch ladder
pass + factorizations + guard tables + optional Gram precompute, returning
a ``PaddedPrecompute`` and the initial ``PaddedState``),
``padded_solve_segment`` (the SAME while_loop body run up to a *traced*
trip limit — one compiled executable re-dispatched per segment),
``finalize_padded_solve`` (the status lattice + certificates), and
``reprecondition_padded`` (rebuild the ladder from replacement level Grams
mid-solve and re-anchor every unfinished problem at its current iterate —
elastic shard recovery). ``padded_adaptive_solve_batched`` is these pieces
composed in one jit with the trip limit pinned at the trip cap, so the
monolithic path is bit-identical to running the segments back-to-back.
The full ``PaddedState`` (iterates, best-iterate, per-problem level,
residual/δ̃ state, counters) is an exported NamedTuple of plain arrays —
exactly what a checkpoint of a preempted solve persists
(``core.robust.segmented_padded_solve_batched``).

Profiling (DESIGN.md §14): each solve piece traces under one named scope —
``engine.sketch``, ``engine.factor``, ``engine.gram``, ``engine.init``,
``engine.loop``, ``engine.finalize`` — which lands in every HLO op's
``op_name`` metadata and so in a device trace. A scope changes no jaxpr
equation, no compiled code and no number.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.precision import canonical_compute_dtype, fp32_contractions

from .level_grams import get_provider
from .precond import shifted_ladder_dual, shifted_ladder_inverses
from .quadratic import Quadratic, gram
from .solvers import c_alpha_rho, rho_to_rate
from .status import SolveStatus

PADDED_METHODS = ("ihs", "pcg", "polyak")


class PaddedState(NamedTuple):
    x: jnp.ndarray            # (B, d) iterates
    x_prev: jnp.ndarray       # (B, d) previous iterate (Polyak momentum)
    r: jnp.ndarray            # (B, d) PCG residual (zeros for IHS)
    rt: jnp.ndarray           # (B, d) PCG preconditioned residual
    p: jnp.ndarray            # (B, d) PCG search direction
    grad: jnp.ndarray         # (B, d) gradient at x (IHS)
    level: jnp.ndarray        # (B,)  index into the doubling ladder (int32)
    t_rel: jnp.ndarray        # (B,)  iterations since last restart
    dtilde_I: jnp.ndarray     # (B,)  δ̃ at last restart
    dtilde: jnp.ndarray       # (B,)  current δ̃
    dtilde0: jnp.ndarray      # (B,)  δ̃ at x₀ under the current sketch
    x_best: jnp.ndarray       # (B, d) best iterate under the current metric
    dt_best: jnp.ndarray      # (B,)  its δ̃ (the returned certificate)
    pinv: jnp.ndarray         # (B, d, d) gathered H_S⁻¹ at the current
                              # level; (B, m_max, d) U_l in the dual form
    iters: jnp.ndarray        # (B,)  accepted iterations
    doublings: jnp.ndarray    # (B,)
    done: jnp.ndarray         # (B,)  bool
    converged: jnp.ndarray    # (B,)  bool: δ̃ cleared tol (honest, not "done")
    nan_hit: jnp.ndarray      # (B,)  bool: a non-finite proposal was seen
    trips: jnp.ndarray        # scalar loop-trip counter


class PaddedPrecompute(NamedTuple):
    """Everything the while_loop body reads that is NOT per-iteration state:
    the factorized ladder, the guard tables and the (optional) precomputed
    true Gram. Produced once per solve by ``prepare_padded_solve`` (or
    inline by ``padded_adaptive_solve_batched``); rebuilt mid-solve only by
    ``reprecondition_padded`` (elastic shard recovery, DESIGN.md §11).
    A pytree of plain arrays — deterministic given (q, keys), so a resumed
    process recomputes it instead of checkpointing O(L·B·d²) bytes."""
    pinvs: jnp.ndarray           # (L, B, d, d) remapped per-level H_S⁻¹;
                                 # (L, B, m_max, d) U_l in the dual form
    remap: jnp.ndarray           # (L, B) valid-level redirect (identity
                                 # when guards are off); −1 ⇒ none valid
    any_valid: jnp.ndarray       # (B,)  problem has ≥1 usable ladder level
    gram_poisoned: jnp.ndarray   # (B,)  some level Gram was non-finite
    invalid_levels: jnp.ndarray  # (B,)  count of skipped ladder levels
    G_full: jnp.ndarray | None   # precomputed AᵀA / AᵀWA ((d, d) shared or
                                 # (B, d, d)); None ⇒ matrix-free hvp
    dscale: jnp.ndarray | None = None  # (B, d) D^{-1/2}; None ⇒ primal


def _apply_pinv(pre: PaddedPrecompute, pinv, z):
    """H_S⁻¹ z at the gathered level — the in-loop hot path: one fused
    batched matvec in the primal form; s ⊙ (y − U_lᵀ(U_l y)), y = s ⊙ z,
    in the dual form (U_l's zero rows past m_l add nothing)."""
    if pre.dscale is None:
        return jnp.einsum("bde,be->bd", pinv, z)
    y = pre.dscale * z
    t = jnp.einsum("bmd,bd->bm", pinv, y)
    return pre.dscale * (y - jnp.einsum("bmd,bm->bd", pinv, t))


def _pdot(a, b):
    return jnp.sum(a * b, axis=-1)


def _is_single_key(keys: jax.Array) -> bool:
    """One PRNG key vs a batch of keys, for both key flavors: typed keys
    (jax.random.key — a key is a rank-0 array) and legacy uint32 keys
    (jax.random.PRNGKey — a key is a rank-1 (2,) array)."""
    if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
        return keys.ndim == 0
    return keys.ndim == 1


def doubling_ladder(m_max: int) -> tuple[int, ...]:
    """The sizes m_t can visit: 1, 2, 4, …, capped at m_max."""
    ms, m = [], 1
    while m < m_max:
        ms.append(m)
        m *= 2
    ms.append(m_max)
    return tuple(ms)


def padded_trip_cap(m_max: int, max_iters: int) -> int:
    """Loop-trip safety cap: rejects per problem are bounded by the ladder
    length, so this is a net on top of the per-problem iteration cap."""
    return max_iters + len(doubling_ladder(m_max)) + 3


def _field_dtype(q: Quadratic):
    return q.A.dtype if q.A.dtype != jnp.int8 else jnp.float32


def _dual_form(q: Quadratic, provider, *, m_max: int, mesh, grams) -> bool:
    """Static choice of the ladder's form (module docstring): dual when
    every level has m < d, the levels are prefixes of one row stream, and
    the call has neither a mesh nor precomputed Grams."""
    return (grams is None and mesh is None and m_max < q.d
            and hasattr(provider, "level_rows"))


def _gather_pinv(pinvs: jnp.ndarray, level: jnp.ndarray) -> jnp.ndarray:
    """Select each problem's preconditioner at its current ladder level."""
    return pinvs[level, jnp.arange(level.shape[0])]


def _valid_level_remap(level_ok: jnp.ndarray):
    """Per-(level, problem) redirect around invalid ladder levels.

    ``level_ok`` (L, B) marks levels whose sketched Gram AND its factorized
    inverse are entirely finite. A level can be individually invalid (a
    rank-deficient low-m sketched Gram under ν ≈ 0 Choleskys to NaN) without
    the problem being hopeless — the doubling controller should *skip* it,
    not let one NaN factor poison the whole solve. ``remap[l, b]`` is the
    nearest valid level ≥ l (the controller only ever moves up the ladder),
    falling back to the largest valid level below when the top of the
    ladder is invalid, and −1 when the problem has NO valid level at all
    (its lattice verdict is ``LEVEL_INVALID``). Both sweeps are one
    associative scan over the ladder axis — O(L·B), free next to the
    factorizations themselves."""
    L = level_ok.shape[0]
    idx = jnp.arange(L, dtype=jnp.int32)[:, None]
    up = jnp.where(level_ok, idx, jnp.int32(L))
    up = jax.lax.associative_scan(jnp.minimum, up, reverse=True, axis=0)
    down = jnp.where(level_ok, idx, jnp.int32(-1))
    down = jax.lax.associative_scan(jnp.maximum, down, axis=0)
    remap = jnp.where(up < L, up, down)          # (L, B); −1 ⇒ none valid
    return remap, jnp.any(level_ok, axis=0)


# ---------------------------------------------------------------------------
# Solve pieces: ladder precompute → init state → segment loop → finalize.
# All traceable; the public jitted entry points below compose them.
# ---------------------------------------------------------------------------

@jax.named_scope("engine.sketch")
def _compute_ladder_rows(q, keys, provider, *, m_max, compute_dtype):
    """(B, m_max, d) fp32 row stream of a prefix family — the ONE touch of
    A in the dual form."""
    data = provider.sample(keys, m_max, q.n, _field_dtype(q))
    R = provider.level_rows(data, q, m_max, compute_dtype=compute_dtype)
    return R.astype(jnp.promote_types(R.dtype, jnp.float32))


@jax.named_scope("engine.sketch")
def _compute_ladder_grams(q, keys, *, m_max, sketch, mesh, compute_dtype):
    """(L, B, d, d) ladder-level Grams — the ONE touch of A."""
    provider = get_provider(sketch)
    ladder = doubling_ladder(m_max)
    if mesh is None:
        data = provider.sample(keys, m_max, q.n, _field_dtype(q))
        return provider.level_grams(data, q, ladder,
                                    compute_dtype=compute_dtype)
    from .distributed import shard_level_grams

    return shard_level_grams(provider, keys, q, ladder, mesh,
                             compute_dtype=compute_dtype)


def _unguarded_tables(pinvs: jnp.ndarray, B: int,
                      **dual) -> PaddedPrecompute:
    """``guards=False``: the identity remap and every level assumed valid
    (the pre-guard hot path, byte-identical gathers)."""
    L = pinvs.shape[0]
    remap = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None], (L, B))
    return PaddedPrecompute(
        pinvs=pinvs, remap=remap, any_valid=jnp.ones((B,), bool),
        gram_poisoned=jnp.zeros((B,), bool),
        invalid_levels=jnp.zeros((B,), jnp.int32), G_full=None, **dual)


def _guard_tables(pinvs, gram_ok, level_ok, fallback) -> PaddedPrecompute:
    """Post-Cholesky validity: a level is usable only if its Gram and its
    factorized table are entirely finite. Invalid levels are skipped via
    the remap (gathers go through the redirected table); problems with NO
    valid level get the table ``fallback`` so their lanes stay finite —
    they are frozen at x₀ before the loop and reported LEVEL_INVALID.
    Non-finite Grams mean poisoned data or a poisoned sketch pass, which
    distinguishes NAN_POISONED from the finite-but-singular LEVEL_INVALID
    verdict when the whole ladder is unusable."""
    gram_poisoned = jnp.any(~gram_ok, axis=0)                       # (B,)
    remap, any_valid = _valid_level_remap(level_ok)
    pinvs = jnp.take_along_axis(
        pinvs, jnp.maximum(remap, 0)[:, :, None, None], axis=0)
    pinvs = jnp.where(any_valid[None, :, None, None], pinvs, fallback)
    invalid_levels = jnp.sum(~level_ok, axis=0).astype(jnp.int32)
    return PaddedPrecompute(
        pinvs=pinvs, remap=remap, any_valid=any_valid,
        gram_poisoned=gram_poisoned, invalid_levels=invalid_levels,
        G_full=None)


@jax.named_scope("engine.factor")
def _ladder_tables(q: Quadratic, grams: jnp.ndarray, *,
                   guards: bool) -> PaddedPrecompute:
    """Primal form: the (L, B, d, d) explicit H_S⁻¹ at every ladder level,
    from the λ-free level Grams (the ν²Λ shift enters only inside
    ``precond.shifted_ladder_inverses``, which is what lets a
    regularization path reuse one ladder across every λ, DESIGN.md §13),
    and the guard tables. ``G_full`` is left None for the caller.

    With the inverses precomputed, the in-loop "refactorization" on a
    doubling is a pure (B,) gather and the per-iteration preconditioner
    application one fused batched matvec — no LAPACK dispatch inside the
    while_loop; the forward error of an explicit inverse is the same
    O(ε·κ) as triangular solves, which a *preconditioner* tolerates."""
    pinvs = shifted_ladder_inverses(grams, q.nu, q.lam_diag)
    if not guards:
        return _unguarded_tables(pinvs, q.batch)
    gram_ok = jnp.all(jnp.isfinite(grams), axis=(-1, -2))           # (L, B)
    level_ok = gram_ok & jnp.all(jnp.isfinite(pinvs), axis=(-1, -2))
    return _guard_tables(pinvs, gram_ok, level_ok,
                         jnp.eye(q.d, dtype=pinvs.dtype))


@jax.named_scope("engine.factor")
def _dual_ladder_tables(q: Quadratic, rows: jnp.ndarray, *, m_max: int,
                        guards: bool) -> PaddedPrecompute:
    """Dual form: the (L, B, m_max, d) U_l of every ladder level
    (``precond.shifted_ladder_dual``) and the guard tables, with the same
    meaning as the primal form's: a level's Gram is finite when its prefix
    of R is, and the level is valid when its U_l is finite too. Level l
    reads only the first m_l rows, so a non-finite row past them touches
    nothing of it. A problem with no valid level gets U = 0 and
    D^{-1/2} = 1 — the identity, as in the primal form."""
    ladder = doubling_ladder(m_max)
    pinvs, dscale = shifted_ladder_dual(rows, ladder, q.nu, q.lam_diag)
    if not guards:
        return _unguarded_tables(pinvs, q.batch, dscale=dscale)
    row_ok = jnp.all(jnp.isfinite(rows), axis=-1)                   # (B, M)
    gram_ok = jnp.stack([jnp.all(row_ok[:, :m], axis=-1) for m in ladder])
    level_ok = gram_ok & jnp.all(jnp.isfinite(pinvs), axis=(-1, -2))
    pre = _guard_tables(pinvs, gram_ok, level_ok, jnp.zeros((), pinvs.dtype))
    return pre._replace(
        dscale=jnp.where(pre.any_valid[:, None], dscale, 1.0))


def _ladder_precompute(q: Quadratic, keys, *, m_max, sketch, mesh, guards,
                       compute_dtype, grams, gram_hvp,
                       gram_full) -> PaddedPrecompute:
    """The sketch pass (unless ``grams`` are given), the ladder tables in
    the form ``_dual_form`` picks, and the optional true Gram."""
    provider = get_provider(sketch)
    if _dual_form(q, provider, m_max=m_max, mesh=mesh, grams=grams):
        rows = _compute_ladder_rows(q, keys, provider, m_max=m_max,
                                    compute_dtype=compute_dtype)
        pre = _dual_ladder_tables(q, rows, m_max=m_max, guards=guards)
    else:
        if grams is None:
            grams = _compute_ladder_grams(q, keys, m_max=m_max,
                                          sketch=sketch, mesh=mesh,
                                          compute_dtype=compute_dtype)
        pre = _ladder_tables(q, grams, guards=guards)
    if gram_full is None:
        gram_full = _gram_precompute(q, gram_hvp, mesh)
    return pre._replace(G_full=gram_full)


@jax.named_scope("engine.gram")
def _gram_precompute(q: Quadratic, gram_hvp: bool | None, mesh):
    """The optional true-Gram precompute behind ``gram_hvp`` (None = auto:
    on when d ≤ min(n, 1024)). Returns the (d, d) / (B, d, d) Gram, or
    None for the matrix-free hvp."""
    if gram_hvp is None:
        gram_hvp = q.d <= min(q.n, 1024)
    if not gram_hvp:
        return None
    w = q.row_weights
    if mesh is None:
        # once, via the chunked compensated Gram: (d, d) for shared A,
        # (B, d, d) per problem — and AᵀWA per problem even with shared A,
        # never through an (n, d) weighted copy of A
        return gram(q.A, w)
    if w is not None:
        from .distributed import shard_weighted_gram

        return shard_weighted_gram(q, mesh)
    if q.shared_A:
        return q.A.T @ q.A                               # (d, d) once
    return jnp.einsum("bnd,bne->bde", q.A, q.A)          # (B, d, d) once


def _hvp_fn(q: Quadratic, G_full):
    """H·v under the precomputed Gram (or q's matrix-free hvp)."""
    if G_full is None:
        return q.hvp
    if G_full.ndim == 2:
        return lambda v: v @ G_full + (q.nu**2)[:, None] * q.lam_diag * v
    return lambda v: jnp.einsum("bde,be->bd", G_full, v) + (
        (q.nu**2)[:, None] * q.lam_diag * v)


@jax.named_scope("engine.init")
def _init_padded_state(q: Quadratic, pre: PaddedPrecompute,
                       init_level, tol, x0=None) -> PaddedState:
    B, d = q.batch, q.d
    fdtype = _field_dtype(q)
    top = pre.remap.shape[0] - 1
    grad_f = lambda x: _hvp_fn(q, pre.G_full)(x) - q.b

    if init_level is None:
        lvl0 = jnp.zeros((B,), jnp.int32)
    else:
        lvl0 = jnp.clip(init_level.astype(jnp.int32), 0, top)
    pinv0 = _gather_pinv(pre.pinvs, lvl0)
    if x0 is None:
        x0 = jnp.zeros((B, d), fdtype)
        g0 = grad_f(x0)                              # = −b
        rt0 = _apply_pinv(pre, pinv0, -g0)
        dtw = 0.5 * _pdot(-g0, rt0)
        dt0 = dtw
        conv0 = dt0 <= tol * dt0                     # trivially-solved (b=0)
    else:
        # Warm start (path mode, DESIGN.md §13): anchor the state at x0,
        # but keep the convergence scale dtilde0 at the COLD b-based δ̃(0)
        # so tol stays relative to the problem, not to how good the warm
        # start already is — the same anchor ``do_refactor`` re-derives
        # after a doubling. A warm start good enough to clear tol·δ̃(0)
        # converges before the loop runs a single trip.
        x0 = x0.astype(fdtype)
        g0 = grad_f(x0)
        rt0 = _apply_pinv(pre, pinv0, -g0)
        dtw = 0.5 * _pdot(-g0, rt0)
        dt0 = 0.5 * _pdot(q.b, _apply_pinv(pre, pinv0, q.b))
        conv0 = dtw <= tol * dt0

    return PaddedState(
        x=x0, x_prev=x0, r=-g0, rt=rt0, p=rt0, grad=g0,
        level=lvl0, t_rel=jnp.zeros((B,), jnp.int32),
        dtilde_I=dtw, dtilde=dtw, dtilde0=dt0,
        x_best=x0, dt_best=dtw, pinv=pinv0,
        iters=jnp.zeros((B,), jnp.int32),
        doublings=jnp.zeros((B,), jnp.int32),
        done=conv0 | ~pre.any_valid,     # no valid level ⇒ frozen at x₀
        converged=conv0,
        nan_hit=jnp.zeros((B,), bool),
        trips=jnp.asarray(0, jnp.int32),
    )


@jax.named_scope("engine.loop")
def _run_segment(q: Quadratic, pre: PaddedPrecompute, st: PaddedState,
                 trip_limit, *, method: str, max_iters: int, rho: float,
                 tol, guards: bool) -> PaddedState:
    """The adaptive while_loop, bounded by ``trip_limit`` (a TRACED trip
    count: the segmented driver re-dispatches this same executable with the
    limit advanced by k per segment; the monolithic solve pins it at the
    trip cap). The body is identical either way, so segment boundaries
    never change the numbers — a segmented solve is bitwise the monolithic
    one."""
    hvp = _hvp_fn(q, pre.G_full)
    grad_f = lambda x: hvp(x) - q.b
    fdtype = _field_dtype(q)
    top = pre.remap.shape[0] - 1

    phi, alpha = rho_to_rate(method, rho)
    c = c_alpha_rho(alpha, rho)
    mu = 1.0 - rho
    # Polyak heavy-ball constants (Appendix A), matching core.solvers
    _sq = math.sqrt(1.0 - rho)
    mu_p = 2.0 * (1.0 - rho) / (1.0 + _sq)
    beta_p = (1.0 - _sq) / (1.0 + _sq)

    def cond(st: PaddedState):
        return (~jnp.all(st.done)) & (st.trips < trip_limit)

    def body(st: PaddedState) -> PaddedState:
        active = ~st.done
        pinv = st.pinv
        # ---- one step of the method under the current preconditioner ----
        if method in ("ihs", "polyak"):
            # rt caches H_S⁻¹(b − Hx) = −H_S⁻¹∇f from the previous trip's
            # δ̃ evaluation (or the restart), so each trip applies the
            # preconditioner once, not twice. Polyak adds the heavy-ball
            # momentum β(x − x_prev); x_prev resets on every restart.
            if method == "ihs":
                x_new = st.x + mu * st.rt
            else:
                x_new = st.x + mu_p * st.rt + beta_p * (st.x - st.x_prev)
            g_new = grad_f(x_new)
            rt_new = _apply_pinv(pre, pinv, -g_new)
            dt_new = 0.5 * _pdot(-g_new, rt_new)
            r_new, p_new = -g_new, st.p
        else:  # pcg
            Hp = hvp(st.p)
            denom = _pdot(st.p, Hp)
            ok = denom > 0
            alpha_s = jnp.where(ok, 2.0 * st.dtilde / jnp.where(ok, denom, 1.0), 0.0)
            x_new = st.x + alpha_s[:, None] * st.p
            r_new = st.r - alpha_s[:, None] * Hp
            rt_new = _apply_pinv(pre, pinv, r_new)
            dt_new = 0.5 * _pdot(r_new, rt_new)
            okb = st.dtilde > 0
            beta = jnp.where(okb, dt_new / jnp.where(okb, st.dtilde, 1.0), 0.0)
            p_new = rt_new + beta[:, None] * st.p
            g_new = -r_new

        # ---- per-problem improvement test (Alg 4.1 line 6) ----
        threshold = c * (phi ** (st.t_rel + 1).astype(fdtype)) * st.dtilde_I
        if guards:
            # a proposal is only acceptable if the iterate itself is finite,
            # not just its δ̃ — the pair (Inf, −Inf) can produce a finite
            # inner product, and an accepted non-finite x would defeat the
            # best-finite-iterate guarantee below
            finite_prop = jnp.isfinite(dt_new) & jnp.all(
                jnp.isfinite(x_new), axis=-1)
        else:
            finite_prop = jnp.isfinite(dt_new)
        bad = ~finite_prop | (dt_new > threshold)
        at_cap = st.level >= top
        reject = bad & active & ~at_cap
        # At the ladder cap the rate test is unenforceable (no further
        # doubling), so steps are accepted freely and the BEST iterate is
        # tracked instead: f32 δ̃-floor oscillation polishes harmlessly,
        # while clear divergence (a divergent method under a too-weak
        # capped preconditioner, e.g. IHS) stalls the problem — the caller
        # reads the shortfall off the returned δ̃ certificate. Without the
        # safeguard a diverging iteration would be "accepted" to overflow.
        # A non-finite proposal at the cap is the per-problem circuit
        # breaker: the problem freezes at its best finite iterate (a
        # non-finite proposal is NEVER accepted, so x_best stays finite for
        # finite inputs) and ``nan_hit`` records the poisoning for the
        # status verdict.
        stalled = active & at_cap & (
            ~finite_prop | (dt_new > 1e6 * st.dt_best))
        accept = active & ~reject & ~stalled
        conv_now = accept & (dt_new <= tol * st.dtilde0)

        aB = accept[:, None]
        improved = accept & (dt_new < st.dt_best)
        st1 = PaddedState(
            x=jnp.where(aB, x_new, st.x),
            x_prev=jnp.where(aB, st.x, st.x_prev),
            r=jnp.where(aB, r_new, st.r),
            rt=jnp.where(aB, rt_new, st.rt),
            p=jnp.where(aB, p_new, st.p),
            grad=jnp.where(aB, g_new, st.grad),
            level=jnp.where(reject, jnp.minimum(st.level + 1, top), st.level),
            t_rel=jnp.where(accept, st.t_rel + 1, st.t_rel),
            dtilde_I=st.dtilde_I,
            dtilde=jnp.where(accept, dt_new, st.dtilde),
            dtilde0=st.dtilde0,
            x_best=jnp.where(improved[:, None], x_new, st.x_best),
            dt_best=jnp.where(improved, dt_new, st.dt_best),
            pinv=st.pinv,
            iters=st.iters + accept.astype(jnp.int32),
            doublings=st.doublings + reject.astype(jnp.int32),
            done=st.done | stalled | conv_now
                 | (st.iters + accept.astype(jnp.int32) >= max_iters),
            converged=st.converged | conv_now,
            nan_hit=st.nan_hit | (active & ~finite_prop),
            trips=st.trips + 1,
        )

        def do_refactor(s: PaddedState) -> PaddedState:
            # Doubling: unmask more rows + restart at the current iterate
            # (Alg 4.1 line 8). "Refactorization" is a pure gather of the
            # precomputed per-level inverses (problems whose level did not
            # change get the identical factor back); the restart residual
            # is the stored gradient (x did not move on a reject), so no
            # extra H·v is needed.
            pinv_new = _gather_pinv(pre.pinvs, s.level)
            res = -s.grad                              # b − Hx at current x
            rt_re = _apply_pinv(pre, pinv_new, res)
            dt_re = 0.5 * _pdot(res, rt_re)
            dt0_re = 0.5 * _pdot(q.b, _apply_pinv(pre, pinv_new, q.b))
            rB = reject[:, None]
            return s._replace(
                pinv=pinv_new,
                r=jnp.where(rB, res, s.r),
                rt=jnp.where(rB, rt_re, s.rt),
                p=jnp.where(rB, rt_re, s.p),
                x_prev=jnp.where(rB, s.x, s.x_prev),   # momentum restart
                t_rel=jnp.where(reject, 0, s.t_rel),
                # δ̃ is metric-dependent: restart best-tracking in the new
                # preconditioner's metric at the current iterate
                x_best=jnp.where(rB, s.x, s.x_best),
                dt_best=jnp.where(reject, dt_re, s.dt_best),
                dtilde_I=jnp.where(reject, dt_re, s.dtilde_I),
                dtilde=jnp.where(reject, dt_re, s.dtilde),
                dtilde0=jnp.where(reject, dt0_re, s.dtilde0),
            )

        return jax.lax.cond(jnp.any(reject), do_refactor, lambda s: s, st1)

    return jax.lax.while_loop(cond, body, st)


@jax.named_scope("engine.finalize")
def _finalize(pre: PaddedPrecompute, st: PaddedState, *, m_max: int):
    """Status lattice + certificates from the terminal (or paused) state."""
    ladder_m = jnp.asarray(doubling_ladder(m_max), jnp.int32)
    B = pre.remap.shape[1]
    # report the level actually used (the remapped gather target), so
    # m_final and warm-start tokens reflect the sketch that produced the
    # certificate rather than a skipped invalid level
    eff_level = jnp.maximum(
        pre.remap[st.level, jnp.arange(B)], 0).astype(jnp.int32)
    status = jnp.where(
        st.converged, jnp.int32(SolveStatus.OK),
        jnp.where(st.nan_hit | pre.gram_poisoned,
                  jnp.int32(SolveStatus.NAN_POISONED),
                  jnp.where(~pre.any_valid,
                            jnp.int32(SolveStatus.LEVEL_INVALID),
                            jnp.int32(SolveStatus.STALLED))))
    stats = {"m_final": ladder_m[eff_level], "iters": st.iters,
             "doublings": st.doublings, "dtilde": st.dt_best,
             "level": eff_level, "trips": st.trips,
             "status": status, "converged": st.converged,
             "stalled": status == jnp.int32(SolveStatus.STALLED),
             "invalid_levels": pre.invalid_levels,
             "ladder_dual": jnp.full((B,), pre.dscale is not None)}
    return st.x_best, stats


# ---------------------------------------------------------------------------
# Public jitted entry points
# ---------------------------------------------------------------------------

@partial(jax.jit,
         static_argnames=("m_max", "sketch", "gram_hvp", "mesh", "guards",
                          "compute_dtype"))
@fp32_contractions
def prepare_padded_solve(
    q: Quadratic,
    keys: jax.Array,
    *,
    m_max: int,
    sketch: str = "gaussian",
    gram_hvp: bool | None = None,
    mesh=None,
    init_level: jax.Array | None = None,
    guards: bool = True,
    compute_dtype: str = "fp32",
    tol: float = 1e-10,
    grams: jnp.ndarray | None = None,
    gram_full: jnp.ndarray | None = None,
    x0: jnp.ndarray | None = None,
):
    """Everything before the loop, as one jitted dispatch: the one-touch
    ladder pass (or ``grams=`` to supply precomputed/recombined level Grams
    — the elastic-recovery path feeds a ``distributed.ShardLadderCache``
    total here, and the path engine the shared λ-free ladder of
    ``prepare_path_ladder``), the batched factorizations + guard tables,
    the optional true-Gram precompute (or ``gram_full=`` to supply it) and
    the initial state — at the origin, or at a warm-start iterate ``x0=``
    (B, d). Returns ``(PaddedPrecompute, PaddedState)`` — both plain-array
    pytrees; the state is what checkpoints persist, the precompute is
    deterministic given (q, keys) and is recomputed on resume."""
    if not q.batched:
        raise ValueError("prepare_padded_solve expects a batched Quadratic")
    B = q.batch
    if _is_single_key(keys):
        keys = jax.random.split(keys, B)
    pre = _ladder_precompute(
        q, keys, m_max=m_max, sketch=sketch, mesh=mesh, guards=guards,
        compute_dtype=canonical_compute_dtype(compute_dtype), grams=grams,
        gram_hvp=gram_hvp, gram_full=gram_full)
    return pre, _init_padded_state(q, pre, init_level, tol, x0=x0)


@partial(jax.jit, static_argnames=("method", "max_iters", "rho", "guards"),
         donate_argnames=("st",))
@fp32_contractions
def padded_solve_segment(
    q: Quadratic,
    pre: PaddedPrecompute,
    st: PaddedState,
    trip_limit,
    *,
    method: str = "ihs",
    max_iters: int = 100,
    rho: float = 0.5,
    tol: float = 1e-10,
    guards: bool = True,
) -> PaddedState:
    """Advance the adaptive loop to ``trip_limit`` total trips (a traced
    int32 scalar — ONE compiled executable serves every segment size and
    every resume point). State round-trips losslessly, so dispatching
    k-trip segments back-to-back is bitwise the monolithic while_loop.

    ``st`` is DONATED: the 20-field state aliases its output buffers, so a
    long segmented solve holds one state's worth of memory instead of two
    per dispatch. Callers must treat the passed state as consumed — the
    host driver (``core.robust``) rebinds it on every segment; anything a
    checkpoint persists is read from the *returned* state."""
    if method not in PADDED_METHODS:
        raise ValueError(
            f"padded engine supports {PADDED_METHODS}, got {method!r}")
    return _run_segment(q, pre, st, jnp.asarray(trip_limit, jnp.int32),
                        method=method, max_iters=max_iters, rho=rho,
                        tol=tol, guards=guards)


@partial(jax.jit, static_argnames=("m_max",))
@fp32_contractions
def finalize_padded_solve(pre: PaddedPrecompute, st: PaddedState, *,
                          m_max: int):
    """(x_best, stats) from a terminal — or deadline-paused — state; the
    certificates (δ̃, m_final, level) describe the best finite iterate
    actually reached, which is what an honest DEADLINE_EXCEEDED answer
    returns."""
    return _finalize(pre, st, m_max=m_max)


@partial(jax.jit, static_argnames=("guards",))
@fp32_contractions
def reprecondition_padded(
    q: Quadratic,
    pre: PaddedPrecompute,
    st: PaddedState,
    grams: jnp.ndarray,
    *,
    guards: bool = True,
):
    """Rebuild the ladder from replacement level Grams MID-SOLVE and
    re-anchor every unfinished problem at its current iterate — the elastic
    shard-recovery step (DESIGN.md §11).

    After a data shard drops, the surviving per-shard level-Gram
    contributions recombine by one subtraction (``ShardLadderCache``);
    this refactors the recombined ladder (batched Cholesky + guard tables,
    exactly the prepare-time path) and then mirrors the in-loop doubling
    restart for every not-done problem: regather H_S⁻¹ at its current
    level, recompute r/r̃/p and the δ̃ anchors from the stored gradient,
    and restart best-iterate tracking in the new metric at the current x.
    The true Hessian (``pre.G_full`` / q) is untouched — the solve still
    targets the ORIGINAL problem exactly; only the preconditioner weakens —
    so a subsequent convergence is an honest ``OK`` with a truthful δ̃.
    Problems already done keep their iterates and verdicts bit-for-bit."""
    new = _ladder_tables(q, grams, guards=guards)
    # validity composes: a problem frozen by the OLD ladder never iterated
    # (and must stay LEVEL_INVALID); one with no valid level in the NEW
    # ladder freezes now at its best finite iterate
    with jax.named_scope("engine.factor"):
        any_valid = pre.any_valid & new.any_valid
        pre2 = new._replace(
            any_valid=any_valid,
            gram_poisoned=pre.gram_poisoned | new.gram_poisoned,
            invalid_levels=jnp.maximum(pre.invalid_levels,
                                       new.invalid_levels),
            G_full=pre.G_full)
    # the re-anchor is an initialization in the new metric
    with jax.named_scope("engine.init"):
        active = ~st.done
        pinv_new = _gather_pinv(pre2.pinvs, st.level)
        res = -st.grad                         # b − Hx at the current x
        rt = _apply_pinv(pre2, pinv_new, res)
        dt = 0.5 * _pdot(res, rt)
        dt0 = 0.5 * _pdot(q.b, _apply_pinv(pre2, pinv_new, q.b))
        aB = active[:, None]
        # the new ladder is primal: a dual-form state's U_l is read by no
        # lane any more, so every lane takes the d×d inverse
        keep = st.pinv if st.pinv.shape == pinv_new.shape else pinv_new
        st2 = st._replace(
            pinv=jnp.where(active[:, None, None], pinv_new, keep),
            r=jnp.where(aB, res, st.r),
            rt=jnp.where(aB, rt, st.rt),
            p=jnp.where(aB, rt, st.p),
            x_prev=jnp.where(aB, st.x, st.x_prev),  # momentum restart
            t_rel=jnp.where(active, 0, st.t_rel),
            x_best=jnp.where(aB, st.x, st.x_best),
            dt_best=jnp.where(active, dt, st.dt_best),
            dtilde_I=jnp.where(active, dt, st.dtilde_I),
            dtilde=jnp.where(active, dt, st.dtilde),
            dtilde0=jnp.where(active, dt0, st.dtilde0),
            done=st.done | (active & ~any_valid),
        )
    return pre2, st2


@partial(jax.jit,
         static_argnames=("m_max", "method", "sketch", "max_iters", "rho",
                          "gram_hvp", "mesh", "guards", "compute_dtype"))
@fp32_contractions
def padded_adaptive_solve_batched(
    q: Quadratic,
    keys: jax.Array,
    *,
    m_max: int,
    method: str = "ihs",
    sketch: str = "gaussian",
    max_iters: int = 100,
    rho: float = 0.5,
    tol: float = 1e-10,
    gram_hvp: bool | None = None,
    mesh=None,
    init_level: jax.Array | None = None,
    guards: bool = True,
    compute_dtype: str = "fp32",
    grams: jnp.ndarray | None = None,
    gram_full: jnp.ndarray | None = None,
    x0: jnp.ndarray | None = None,
):
    """One-executable adaptive solve of a batch of B problems.

    ``q`` must be batched (per-problem A (B,n,d) or shared A (n,d));
    ``keys`` is a single PRNG key (split internally) or a (B,)-batch of keys
    — problem b's sketch depends only on keys[b]. Returns (x, stats) with
    x (B, d) and per-problem stats vectors (m_final, iters, doublings, δ̃,
    and the final ladder ``level`` index — what a warm restart passes back).

    ``q.row_weights`` (B, n) solves the *weighted* problem
    H = AᵀWA + ν²Λ: the providers sketch W^{1/2}A inside their one
    streaming pass (scaling generated S tiles / sign streams by w^{1/2} —
    never an (n, d) weighted copy of A, DESIGN.md §8) and the hvp applies
    the weight on the (B, n) intermediate. This is the GLM Newton
    subproblem layout (``core.newton``).

    ``init_level`` (B,) int32 starts each problem's doubling ladder at the
    given level instead of 0 — the warm-started m_t of the adaptive Newton
    sketch (arXiv:2105.07291): a Newton driver passes the previous outer
    step's final level so the inner solve does not re-climb the ladder it
    already discovered. Values are clipped to the ladder; a traced array,
    so warm restarts reuse the same executable.

    ``gram_hvp`` (default: auto, on when d ≤ min(n, 1024)): precompute the
    per-problem Gram AᵀA once so every in-loop H·v is a (B,d,d)·(B,d)
    matvec instead of two memory-bound (B,n,d) GEMVs — the right trade in
    the serving regime (n ≫ d, many iterations), and no more than the
    sketch pass we already pay; large-d problems keep the matrix-free O(nd)
    hvp of the paper.

    ``guards`` (static, default on): the failure-isolation layer
    (DESIGN.md §9). Post-Cholesky finiteness checks mark individual ladder
    levels invalid and the controller *skips* them (``_valid_level_remap``)
    instead of letting one NaN factor poison the solve; iterate proposals
    are finiteness-checked so a non-finite step is rejected (doubling below
    the cap, circuit-breaking at it) and the best FINITE iterate is always
    what is returned; every problem exits with a truthful per-problem
    ``status`` ∈ {OK, STALLED, LEVEL_INVALID, NAN_POISONED} plus explicit
    ``converged``/``stalled`` flags. ``guards=False`` restores the
    pre-guard hot path (no level remap, δ̃-only finiteness) for overhead
    benchmarking (``benchmarks/bench_guard.py``); statuses are still
    reported but ladder validity is assumed.

    ``compute_dtype`` (static, ``kernels.precision``): precision of the
    one-touch sketch pass only — ``"bf16"`` streams/contracts sketch
    operands in bfloat16 with fp32 accumulation, ``"int8"`` additionally
    quantizes A per row and streams the codes. The (L, B, d, d) ladder
    Grams, their Cholesky factors, every in-loop quantity and the δ̃
    certificates are fp32 in all modes, so guards and the certificate
    contract are unchanged; the sketch is merely a (slightly) noisier
    spectral approximation, which the doubling controller absorbs
    (DESIGN.md §10). The fp32 default is bit-identical to the
    pre-dtype-axis engine.

    ``mesh`` (static): a ``jax.sharding.Mesh`` whose data axes row-shard A
    (``distributed.shard_quadratic`` places it). The ONLY thing that
    changes is the precompute: the one-touch ladder pass runs per shard
    with independent per-shard randomness and combines the (L, B, d, d)
    level Grams in ONE psum (``distributed.shard_level_grams``,
    DESIGN.md §5); the while_loop is byte-identical, operating on the
    replicated d-sized state. With ``gram_hvp`` (the serving default) the
    AᵀA precompute is the only other data-axis collective and the loop
    itself is collective-free; matrix-free mode keeps one psum(B·d) per
    hvp, inserted by GSPMD.

    ``grams`` / ``gram_full`` / ``x0`` (traced, path mode — DESIGN.md §13):
    supply a precomputed λ-free ladder of level Grams (L, B, d, d), the
    precomputed true Gram, and/or a warm-start iterate (B, d). With
    ``grams=`` the one-touch sketch pass is SKIPPED — the λ sweep of
    ``padded_path_solve_batched`` pays it once via ``prepare_path_ladder``
    and re-solves every λ point off the shared ladder, with only the
    ν²Λ-shifted factorizations repeated per point.

    This function is ``prepare_padded_solve`` → ``padded_solve_segment``
    (with the trip limit pinned at the trip cap) → ``finalize_padded_solve``
    composed in one jit — bit-identical to dispatching the segments
    separately (``core.robust.segmented_padded_solve_batched``, the
    preemptible/deadline-aware host driver).
    """
    if not q.batched:
        raise ValueError("use padded_adaptive_solve for single problems")
    if method not in PADDED_METHODS:
        raise ValueError(f"padded engine supports {PADDED_METHODS}, got {method!r}")
    B = q.batch
    if _is_single_key(keys):
        keys = jax.random.split(keys, B)
    pre = _ladder_precompute(
        q, keys, m_max=m_max, sketch=sketch, mesh=mesh, guards=guards,
        compute_dtype=canonical_compute_dtype(compute_dtype), grams=grams,
        gram_hvp=gram_hvp, gram_full=gram_full)
    init = _init_padded_state(q, pre, init_level, tol, x0=x0)
    st = _run_segment(q, pre, init, padded_trip_cap(m_max, max_iters),
                      method=method, max_iters=max_iters, rho=rho, tol=tol,
                      guards=guards)
    return _finalize(pre, st, m_max=m_max)


@partial(jax.jit,
         static_argnames=("m_max", "sketch", "gram_hvp", "mesh",
                          "compute_dtype"))
@fp32_contractions
def prepare_path_ladder(
    q: Quadratic,
    keys: jax.Array,
    *,
    m_max: int,
    sketch: str = "gaussian",
    gram_hvp: bool | None = None,
    mesh=None,
    compute_dtype: str = "fp32",
):
    """The λ-FREE precompute shared by an entire regularization path: the
    one-touch ladder pass (under ``mesh``, the same per-shard pass + ONE
    psum of the (L, B, d, d) level Grams) plus the optional true-Gram
    precompute. Neither output reads q.nu / q.lam_diag — the ν²Λ shift
    enters only at factorization (``precond.shifted_ladder_inverses``) —
    so the returned ``(grams, gram_full)`` pair serves EVERY λ point of a
    grid: feed it to ``prepare_padded_solve`` / the batched solver via
    ``grams=`` / ``gram_full=`` (DESIGN.md §13). This is also the unit the
    serving ladder cache stores per (A, Λ, family, dtype) fingerprint.

    ``gram_full`` is None when the hvp stays matrix-free (``gram_hvp``
    auto-off for large d) — pass the pair through unchanged either way."""
    if not q.batched:
        raise ValueError("prepare_path_ladder expects a batched Quadratic")
    if _is_single_key(keys):
        keys = jax.random.split(keys, q.batch)
    compute_dtype = canonical_compute_dtype(compute_dtype)
    grams = _compute_ladder_grams(q, keys, m_max=m_max, sketch=sketch,
                                  mesh=mesh, compute_dtype=compute_dtype)
    return grams, _gram_precompute(q, gram_hvp, mesh)


def padded_path_solve_batched(
    q: Quadratic,
    keys: jax.Array,
    nus: jnp.ndarray,
    *,
    m_max: int,
    method: str = "ihs",
    sketch: str = "gaussian",
    max_iters: int = 100,
    rho: float = 0.5,
    tol: float = 1e-10,
    gram_hvp: bool | None = None,
    mesh=None,
    init_level: jax.Array | None = None,
    guards: bool = True,
    compute_dtype: str = "fp32",
    warm_start: bool = True,
):
    """Regularization-path solve: the full λ grid off ONE sketch pass.

    ``q`` is a batched Quadratic (B problems; its own ``q.nu`` is ignored)
    and ``nus`` is the λ grid — (P,) shared across the batch, or (P, B)
    per-problem. Because the ladder-level Grams are λ-free, the one-touch
    sketch pass (and the optional true-Gram precompute) runs ONCE via
    ``prepare_path_ladder``; each grid point then pays only the ν²Λ-shifted
    factorizations (``precond.shifted_ladder_inverses``) and its solve —
    a P-point path costs ~1 sketch pass instead of P (DESIGN.md §13).

    ``warm_start`` (default on) carries both the iterate x AND the
    per-problem sketch level from the previous grid point: point p+1
    starts at x_p with ``init_level`` = the final ladder level of point p
    (the traced warm-start hook), so a grid walked from strong to weak
    regularization never re-climbs the ladder — level trajectories are
    monotone along the path. The convergence scale stays each point's
    cold δ̃(0), so certificates mean the same thing warm or cold.
    ``init_level`` seeds the FIRST point (e.g. from a previous path).

    Each point is solved by ``padded_adaptive_solve_batched`` with
    ``grams=`` / ``gram_full=`` supplied, so per-point numbers are
    bit-identical to a single-λ solve handed the same shared ladder,
    warm start and init level; ``guards`` semantics are per point.

    Returns ``(xs, stats)``: xs (P, B, d) and stats with the per-point
    engine vectors stacked to (P, B) (``trips`` to (P,)), plus
    ``sketch_passes`` = 1 — the whole grid touched A once."""
    if not q.batched:
        raise ValueError("padded_path_solve_batched expects a batched "
                         "Quadratic")
    fdtype = _field_dtype(q)
    nus = jnp.asarray(nus, fdtype)
    if nus.ndim == 1:
        nus = jnp.broadcast_to(nus[:, None], (nus.shape[0], q.batch))
    P = nus.shape[0]
    if _is_single_key(keys):
        keys = jax.random.split(keys, q.batch)
    grams, gram_full = prepare_path_ladder(
        q, keys, m_max=m_max, sketch=sketch, gram_hvp=gram_hvp, mesh=mesh,
        compute_dtype=compute_dtype)
    xs, per_point = [], []
    x_prev, lvl = None, init_level
    for p in range(P):
        q_p = dataclasses.replace(q, nu=nus[p])
        x, stats = padded_adaptive_solve_batched(
            q_p, keys, m_max=m_max, method=method, sketch=sketch,
            max_iters=max_iters, rho=rho, tol=tol, gram_hvp=gram_hvp,
            mesh=mesh, init_level=lvl, guards=guards,
            compute_dtype=compute_dtype, grams=grams, gram_full=gram_full,
            x0=x_prev)
        xs.append(x)
        per_point.append(stats)
        if warm_start:
            x_prev, lvl = x, stats["level"]
    out = {k: jnp.stack([s[k] for s in per_point]) for k in per_point[0]}
    out["sketch_passes"] = 1
    return jnp.stack(xs), out


def padded_adaptive_solve(
    q: Quadratic,
    key: jax.Array,
    *,
    m_max: int,
    method: str = "ihs",
    sketch: str = "gaussian",
    max_iters: int = 100,
    rho: float = 0.5,
    tol: float = 1e-10,
    compute_dtype: str = "fp32",
):
    """Adaptive solve of one problem as a B=1 (or B=c for matrix RHS) batch
    through the padded multi-problem engine. Returns (x, stats) with scalar
    stats for vector right-hand sides; a (d, c) matrix RHS is dispatched as
    a shared-A batch over columns and gets per-column stats.

    The host work around the engine call runs under three profiler spans,
    ``repro.solve.prepare`` (the batch-of-one views), ``.dispatch`` (the
    jitted engine call) and ``.unpack`` (x and the stats); with no
    profiler active each costs under a microsecond (DESIGN.md §14)."""
    if q.batched:
        return padded_adaptive_solve_batched(
            q, key, m_max=m_max, method=method, sketch=sketch,
            max_iters=max_iters, rho=rho, tol=tol,
            compute_dtype=compute_dtype)
    span = jax.profiler.TraceAnnotation
    with span("repro.solve.prepare"):
        matrix_rhs = q.b.ndim == 2
        if matrix_rhs:
            B = q.b.shape[1]
            b = q.b.T
            keys = jax.random.split(key, B)
        else:
            B = 1
            b = q.b[None, :]
            keys = key[None] if _is_single_key(key) else key
        nu = jnp.broadcast_to(jnp.atleast_1d(q.nu), (B,))
        lam = jnp.broadcast_to(q.lam_diag, (B, q.d))
        w = (None if q.row_weights is None
             else jnp.broadcast_to(q.row_weights, (B, q.n)))
        qb = Quadratic(A=q.A, b=b, nu=nu, lam_diag=lam, batched=True,
                       row_weights=w)
    with span("repro.solve.dispatch"):
        x, stats = padded_adaptive_solve_batched(
            qb, keys, m_max=m_max, method=method, sketch=sketch,
            max_iters=max_iters, rho=rho, tol=tol,
            compute_dtype=compute_dtype)
    with span("repro.solve.unpack"):
        if matrix_rhs:
            return x.T, stats
        return x[0], {k: (v[0] if getattr(v, "ndim", 0) else v)
                      for k, v in stats.items()}
