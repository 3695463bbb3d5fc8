"""Ladder-level Gram providers for the padded adaptive engine.

The padded engine (``core.adaptive_padded``) precomputes the sketched Gram
(S_m A)ᵀ(S_m A) at every doubling-ladder level {1, 2, 4, …, m_max} before
its while_loop starts. Each sketch family owns its ladder algebra — how a
single fixed-randomness pass over A yields a *consistent* sketch at every
level — behind one protocol (DESIGN.md §6):

* ``sample(keys, m_max, n, dtype)`` → per-problem randomness (a dict of
  (B, …) arrays), one key per problem so a batched run reproduces the
  corresponding single-problem runs;
* ``level_grams(data, q, ladder)`` → (L, B, d, d) Grams, touching A
  exactly ONCE (the paper's O(sketch) + Σ O(factorize) accounting).

The prefix families (``gaussian``, ``gaussian_dense``, ``srht``), whose
level-m sketch is the first m rows of one row stream, also expose
``level_rows(data, q, m_max)`` → that (B, m_max, d) stream R, with the
level-m Gram R[:m]ᵀR[:m]/m; their ``level_grams`` is
``prefix_level_grams(level_rows(...))``. The engine factors a ladder whose
levels all have m < d from R itself, in the m×m dual form
(``core.adaptive_padded``, DESIGN.md §6).

The level Grams are λ-FREE: no provider reads ``q.nu`` / ``q.lam_diag``
— the ν²Λ shift enters only at factorization
(``precond.shifted_ladder_inverses``). That is what lets one ladder
stack serve an entire regularization path and the serving ladder cache
key on (A, Λ, family, dtype) alone (DESIGN.md §13).

Families:

* ``gaussian`` — *streamed*: rows are generated on the fly from a
  counter-based PRNG fused with the A contraction
  (``kernels.gaussian_gram``); S never exists in HBM, A is streamed once
  in n-chunks, live memory is O(B·m_max·d + B·d²·L). Masking = prefix of
  the i.i.d. row stream; the level-m rescale 1/√m folds into 1/m on the
  Gram.
* ``gaussian_dense`` — the same sketch entries, materialized as a
  (B, m_max, n) array and contracted by einsum. Kept as the memory
  baseline for benchmarks/tests; the streamed provider must match it to
  fp reduction error at every level.
* ``sjlt`` — each data row i carries a fixed uniform u_i and a sign; the
  level-m target row ⌊u_i·m⌋ is exactly uniform for every m, and
  ⌊u·m⌋ = ⌊⌊u·2m⌋/2⌋ makes each pow2 level an exact pairwise row-fold of
  the level above. ONE dispatch at M = 2^⌈log₂ m_max⌉ (the Pallas MXU
  kernel on TPU), then log₂ cheap folds. A non-pow2 cap level is derived
  from the SAME dispatch by folding the M − m_max tail rows back onto the
  head (row j ≥ m_max dispatches to j − m_max): still one ±1 per column,
  so SᵀS = I exactly; the first M − m_max target rows are 2× likelier
  than the rest, which perturbs embedding constants only — and A is
  touched exactly once.
* ``srht`` — signs + a row-sample stream FIXED at m_max: one sign flip,
  one FWHT pass (the paper's O(n·d·log n) embedding; ``fwht_pallas`` on
  TPU, the jnp butterfly elsewhere) touching A once, then level-m = the
  first m sampled rows. Rows are i.i.d. uniform over the padded index
  space, so a prefix of the stream is a valid m-row sample for EVERY m —
  the same argument as the SJLT's ⌊u·m⌋. The 1/√m rescale folds into 1/m
  on the prefix-summed row-Grams, exactly as for the Gaussian.

Row weights (DESIGN.md §8): when the problem carries ``row_weights`` w
(the GLM Newton subproblem's Hessian weights), every family sketches
W^{1/2}A instead of A *inside the same single pass*: the Gaussian scales
its generated S tiles by w^{1/2} in-stream, the SJLT folds w^{1/2} into
its one-nonzero-per-column sign stream, and the SRHT folds w^{1/2} into
the sign flip that precedes the FWHT. No family materializes an (n, d)
weighted copy of A, and the one-touch ladder algebra is untouched — the
weight is a property of the sketch application, not of the ladder.

Compute dtype (DESIGN.md §10, ``kernels.precision``): every provider takes
``compute_dtype ∈ {"fp32", "bf16", "int8"}`` and applies it to the SKETCH
PASS only — bf16 operands with fp32 accumulation, or an int8-quantized A
stream whose per-row dequantization scales fold into the same per-row
scale slot the GLM weights use. The (L, B, d, d) level Grams this module
returns are always fp32: the ladder's Cholesky factors, guards, and δ̃
certificates downstream never see reduced precision.
"""

from __future__ import annotations

from typing import Protocol

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.gaussian_gram import gaussian_s_dense, resolve_stream
# COMPUTE_DTYPES is a deliberate re-export (launch/serve, examples,
# benchmarks all import it from here)
from repro.kernels.precision import COMPUTE_DTYPES, canonical_compute_dtype  # noqa: F401

from .quadratic import Quadratic


class LevelGramProvider(Protocol):
    """A sketch family's ladder algebra (see module docstring)."""

    name: str

    def sample(self, keys: jax.Array, m_max: int, n: int, dtype) -> dict:
        """Per-problem sketch randomness, one key per problem."""
        ...

    def level_grams(self, data: dict, q: Quadratic,
                    ladder: tuple[int, ...],
                    row_weights: jnp.ndarray | None = None,
                    compute_dtype: str | None = None) -> jnp.ndarray:
        """(L, B, d, d) fp32 Grams (S_m W^{1/2}A)ᵀ(S_m W^{1/2}A); touches A
        exactly once. ``row_weights`` (B, n) overrides ``q.row_weights``
        (defaulting to it); W = I when both are None. ``compute_dtype``
        selects the sketch pass's stream precision (module docstring);
        the returned Grams are fp32 in every mode."""
        ...


def _weights(q: Quadratic, row_weights) -> jnp.ndarray | None:
    return q.row_weights if row_weights is None else row_weights


def prefix_level_grams(R: jnp.ndarray, ladder: tuple[int, ...], *,
                       inv_m_scale: bool) -> jnp.ndarray:
    """(L, B, d, d) Grams from a (B, m_max, d) row stream whose level-m
    sketch is the first m rows: prefix-summed per-segment row-Grams, with
    the per-level 1/√m entry rescale folded in as 1/m when requested.
    A bf16 row stream (non-fp32 ``compute_dtype`` paths) accumulates into
    an fp32 Gram via ``preferred_element_type`` — the precision boundary
    of the whole dtype axis."""
    B, _, d = R.shape
    dtype = jnp.promote_types(R.dtype, jnp.float32)
    grams, acc, prev = [], jnp.zeros((B, d, d), dtype), 0
    for m in ladder:
        seg = R[:, prev:m, :]
        acc = acc + jnp.einsum("bmd,bme->bde", seg, seg,
                               preferred_element_type=dtype)
        grams.append(acc / jnp.asarray(m, dtype) if inv_m_scale else acc)
        prev = m
    return jnp.stack(grams)


class _PrefixLevelGrams:
    """``level_grams`` of a family whose levels are prefixes of one row
    stream. The family's ``level_rows(data, q, m_max, row_weights=None,
    compute_dtype=None)`` returns that (B, m_max, d) stream R of
    S_{m_max} W^{1/2}A without the 1/√m rescale, touching A once; the
    level-m Gram is R[:, :m]ᵀR[:, :m]/m."""

    def level_grams(self, data, q, ladder, row_weights=None,
                    compute_dtype=None):
        R = self.level_rows(data, q, ladder[-1], row_weights=row_weights,
                            compute_dtype=compute_dtype)
        return prefix_level_grams(R, ladder, inv_m_scale=True)


def _uint32_seeds(keys: jax.Array) -> jnp.ndarray:
    """One uint32 counter-hash seed per problem key."""
    return jax.vmap(lambda k: jax.random.bits(k, dtype=jnp.uint32))(keys)


class GaussianStreamedProvider(_PrefixLevelGrams):
    """Streaming fused sketch→Gram (the default ``gaussian`` family)."""

    name = "gaussian"

    def sample(self, keys, m_max, n, dtype):
        return {"seeds": _uint32_seeds(keys)}

    def level_rows(self, data, q, m_max, row_weights=None,
                   compute_dtype=None):
        return ops.gaussian_sa(q.A, data["seeds"], m_max,
                               row_weights=_weights(q, row_weights),
                               compute_dtype=compute_dtype)


class GaussianDenseProvider(_PrefixLevelGrams):
    """Materialized-S baseline: identical sketch entries, O(B·m_max·n)."""

    name = "gaussian_dense"

    def sample(self, keys, m_max, n, dtype):
        return {"seeds": _uint32_seeds(keys)}

    def level_rows(self, data, q, m_max, row_weights=None,
                   compute_dtype=None):
        B = data["seeds"].shape[0]
        # same per-row scale algebra as the streamed provider: w^{1/2} and
        # int8 dequantization scales merge into one (B, n) column scale on
        # the materialized S (fp32, applied before the contract-dtype cast)
        A, scale, ct, _ = resolve_stream(q.A, B, _weights(q, row_weights),
                                         compute_dtype)
        S = gaussian_s_dense(data["seeds"], m_max, q.n).astype(jnp.float32)
        if scale is not None:
            S = S * scale[:, None, :]
        if q.shared_A:
            SA = jnp.einsum("bmn,nd->bmd", S.astype(ct), A.astype(ct),
                            preferred_element_type=jnp.float32)
        else:
            SA = jnp.einsum("bmn,bnd->bmd", S.astype(ct), A.astype(ct),
                            preferred_element_type=jnp.float32)
        return SA


class SJLTProvider:
    """s=1 SJLT ladder: one dispatch at the top power of two, folds below."""

    name = "sjlt"

    def sample(self, keys, m_max, n, dtype):
        u = jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, 0), (n,), dtype))(keys)
        signs = jax.vmap(lambda k: jax.random.rademacher(
            jax.random.fold_in(k, 1), (n,), dtype))(keys)
        return {"u": u, "signs": signs}

    def level_grams(self, data, q, ladder, row_weights=None,
                    compute_dtype=None):
        u, signs = data["u"], data["signs"]
        m_max = ladder[-1]
        M = 1 << max(0, (m_max - 1).bit_length())   # top pow2 ≥ m_max
        rows = jnp.clip(
            jnp.floor(u * jnp.asarray(M, u.dtype)).astype(jnp.int32),
            0, M - 1)
        SA = ops.sjlt_apply_batched(                       # the ONE touch
            q.A, rows, signs, M, row_weights=_weights(q, row_weights),
            compute_dtype=compute_dtype)
        by_m = {M: SA}
        m = M
        while m > 1:                    # ⌊u·m⌋ = ⌊⌊u·2m⌋/2⌋: pairwise fold
            SA = SA[:, 0::2, :] + SA[:, 1::2, :]
            m //= 2
            by_m[m] = SA
        if m_max != M:                  # non-pow2 cap: fold the tail rows
            top = by_m[M]
            head, tail = top[:, :m_max, :], top[:, m_max:, :]
            by_m[m_max] = head + jnp.pad(
                tail, ((0, 0), (0, 2 * m_max - M), (0, 0)))
        return jnp.stack(
            [jnp.einsum("bmd,bme->bde", by_m[m], by_m[m]) for m in ladder])


class SRHTProvider(_PrefixLevelGrams):
    """SRHT ladder: one FWHT pass, level-m = first m of a fixed row stream.

    Row-sampling law: rows are i.i.d. uniform over the padded index space
    WITH replacement (``randint``) — a prefix of an i.i.d. stream is a
    valid m-row sample for EVERY ladder level, which is what makes the
    one-touch ladder work. ``kernels.ops.srht_sketch`` (the fixed-size
    sketch) instead samples WITHOUT replacement, the classical SRHT; both
    satisfy E[SᵀS] = I, and the laws agree in the sparse regime
    m ≪ n_pad where collisions are rare. Pinned by tests/test_sharded.py.
    """

    name = "srht"

    def sample(self, keys, m_max, n, dtype):
        n_pad = 1 << max(0, (n - 1).bit_length())
        signs = jax.vmap(lambda k: jax.random.rademacher(
            jax.random.fold_in(k, 0), (n,), dtype))(keys)
        rows = jax.vmap(lambda k: jax.random.randint(
            jax.random.fold_in(k, 1), (m_max,), 0, n_pad))(keys)
        return {"signs": signs, "rows": rows}

    def level_rows(self, data, q, m_max, row_weights=None,
                   compute_dtype=None):
        signs, rows = data["signs"], data["rows"]
        n, d = q.n, q.d
        B = signs.shape[0]
        n_pad = 1 << max(0, (n - 1).bit_length())
        w = _weights(q, row_weights)
        # signs (and, when weighted, w^{1/2}) fold into ONE per-row scale
        # fused into the FWHT kernel's VMEM tile — the sign-flipped /
        # weighted copy of A never round-trips HBM on the Pallas path
        scale = signs if w is None else signs * jnp.sqrt(w).astype(
            signs.dtype)
        A = q.A
        if (canonical_compute_dtype(compute_dtype) == "int8"
                and A.dtype != jnp.int8):
            # quantize before pad/broadcast so the padded copy is 1 B/elem;
            # dequantization scales join the fused per-row scale
            from repro.dist.compress import quantize_rows

            A, a_scales = quantize_rows(A)
            if q.shared_A:
                a_scales = jnp.broadcast_to(a_scales[None, :], (B, n))
            scale = scale * a_scales
        X = A if not q.shared_A else jnp.broadcast_to(
            A[None, :, :], (B, n, d))
        if n_pad != n:
            X = jnp.pad(X, ((0, 0), (0, n_pad - n), (0, 0)))
            scale = jnp.pad(scale, ((0, 0), (0, n_pad - n)))
        HX = ops.fwht_cols(X, row_scale=scale,             # the ONE touch
                           compute_dtype=compute_dtype)
        return jnp.take_along_axis(HX, rows[:, :, None], axis=1)


class BlockEmulationProvider:
    """Single-device emulation of the sharded *concatenated* block sketch
    (DESIGN.md §5): shard k applies ``inner`` with ``fold_in(key, k)``
    randomness to rows [k·n/K, (k+1)·n/K) and the level Grams sum — the
    replicated reference for ``distributed.shard_level_grams`` (identical
    math, identical per-shard keys, no mesh), used by the multi-device
    tests and as the 1-device baseline in ``benchmarks/bench_sharded.py``.
    Pass the instance itself as the engine's ``sketch=``.

    ``drop_shards``: simulate shard dropout (DESIGN.md §9) — the listed
    shard indices contribute NOTHING to the level-Gram sum, exactly the
    K−1-block re-psum a pod performs after losing a data shard. The
    resulting Grams are still valid sketches of the SURVIVING rows, so the
    preconditioner is merely weaker, not wrong — unless the lost rows
    carried the dominant mass, in which case the engine's guards (stall
    detection → retry → fallback) are what keep the answer honest; the
    chaos suite (``tests/test_faults.py``) exercises both regimes."""

    def __init__(self, inner: "LevelGramProvider | str", n_shards: int,
                 drop_shards: tuple[int, ...] = ()):
        self.inner = get_provider(inner)
        self.n_shards = n_shards
        self.drop_shards = tuple(sorted(set(drop_shards)))
        if any(k < 0 or k >= n_shards for k in self.drop_shards):
            raise ValueError(
                f"drop_shards {drop_shards} out of range for {n_shards}")
        if len(self.drop_shards) >= n_shards:
            raise ValueError("cannot drop every shard")
        drop = (f"-drop{list(self.drop_shards)}" if self.drop_shards else "")
        self.name = f"block[{self.inner.name}x{n_shards}{drop}]"

    def _check(self, n: int) -> int:
        if n % self.n_shards:
            raise ValueError(
                f"n={n} not divisible by {self.n_shards} emulated shards")
        return n // self.n_shards

    def sample(self, keys, m_max, n, dtype):
        n_loc = self._check(n)
        return {"shards": [
            self.inner.sample(
                jax.vmap(lambda kb: jax.random.fold_in(kb, k))(keys),
                m_max, n_loc, dtype)
            for k in range(self.n_shards)
        ]}

    def level_grams(self, data, q, ladder, row_weights=None,
                    compute_dtype=None):
        n_loc = self._check(q.n)
        w = q.row_weights if row_weights is None else row_weights
        out = None
        for k, dk in enumerate(data["shards"]):
            if k in self.drop_shards:       # lost shard: absent from psum
                continue
            A_k = q.A[..., k * n_loc:(k + 1) * n_loc, :]
            w_k = None if w is None else w[:, k * n_loc:(k + 1) * n_loc]
            q_k = Quadratic(A=A_k, b=q.b, nu=q.nu, lam_diag=q.lam_diag,
                            batched=q.batched, row_weights=w_k)
            # per-shard reduced-precision pass; the (fp32) shard Grams sum
            # exactly — the emulated analogue of "bf16 passes, fp32 psum"
            g_k = self.inner.level_grams(dk, q_k, ladder,
                                         compute_dtype=compute_dtype)
            out = g_k if out is None else out + g_k
        return out


_PROVIDERS: dict[str, LevelGramProvider] = {
    p.name: p for p in (
        GaussianStreamedProvider(),
        GaussianDenseProvider(),
        SJLTProvider(),
        SRHTProvider(),
    )
}

PADDED_SKETCHES = tuple(_PROVIDERS)


def get_provider(sketch) -> LevelGramProvider:
    """Resolve a sketch-family name to its (stateless) provider; provider
    instances (e.g. a ``BlockEmulationProvider``) pass through unchanged."""
    if not isinstance(sketch, str):
        return sketch
    try:
        return _PROVIDERS[sketch]
    except KeyError:
        raise ValueError(
            f"padded engine supports {PADDED_SKETCHES}, got {sketch!r}"
        ) from None
