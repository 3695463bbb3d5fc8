"""jit'd public wrappers for the Pallas kernels.

On a TPU the kernels always run compiled: ``use_pallas=False`` or
``interpret=True`` there is an error, never a quiet fallback to a
reference. On other backends ``use_pallas=None`` selects the jnp
references, and ``use_pallas=True`` runs the kernel bodies in the Pallas
interpreter (``interpret`` defaults to True off-TPU) — how the tests check
kernel semantics on a CPU. ``use_pallas=True, interpret=False`` compiles
for a TPU target even from a CPU process (the compile rehearsal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import ref
from .fwht import fwht_pallas
from .gaussian_gram import gaussian_sa_pallas, gaussian_sa_ref
from .precision import canonical_compute_dtype, contract_dtype
from .sjlt import fold_row_weights as sjlt_fold_row_weights
from .sjlt import sjlt_pallas, sjlt_pallas_batched

# largest n one in-VMEM FWHT pass takes: an (n, 128) fp32 block is 8 MiB,
# and the pass needs 33 MiB of scoped VMEM on v5e (fwht.vmem_bytes)
_FWHT_VMEM_MAX_N = 16_384


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_mode(use_pallas: bool | None,
                 interpret: bool | None) -> tuple[bool, bool]:
    """Resolve (use_pallas, interpret) for one call (see module doc)."""
    if _on_tpu():
        if use_pallas is False or interpret:
            raise ValueError(
                "on a TPU the Pallas kernels run compiled; use_pallas=False "
                "and interpret=True are for other backends")
        return True, False
    return bool(use_pallas), (True if interpret is None else interpret)


@functools.partial(jax.jit,
                   static_argnames=("use_pallas", "interpret",
                                    "compute_dtype"))
def fwht(x: jnp.ndarray, *, use_pallas: bool | None = None,
         interpret: bool | None = None,
         row_scale: jnp.ndarray | None = None,
         compute_dtype: str | None = None) -> jnp.ndarray:
    """Unnormalized FWHT along axis 0 (n power of two). ``row_scale`` (n,)
    computes H·diag(s)·x — fused into the kernel's VMEM tile on the Pallas
    path (SRHT signs and GLM w^{1/2} ride along for free).

    ``compute_dtype`` (``kernels.precision``): bf16/int8 modes run the
    butterfly passes in bfloat16 — the tile (and fused scale) is cast
    in-register, halving the transform's VMEM/HBM footprint; an int8 ``x``
    (quantized codes ≤ 127, exact in bf16) rides the same cast. The final
    Gram contraction downstream stays fp32 (the SRHT provider's einsum)."""
    use_pallas, interpret = _kernel_mode(use_pallas, interpret)
    if canonical_compute_dtype(compute_dtype) != "fp32":
        ct = contract_dtype(compute_dtype)
        x = x.astype(ct)
        if row_scale is not None:
            row_scale = row_scale.astype(ct)
    n = x.shape[0]
    if not use_pallas:
        if row_scale is not None:
            x = x * row_scale[:, None].astype(x.dtype)
        return ref.fwht_ref(x)
    if n <= _FWHT_VMEM_MAX_N:
        return fwht_pallas(x, interpret=interpret, row_scale=row_scale)
    if row_scale is not None:
        x = x * row_scale[:, None].astype(x.dtype)
    return fwht_large(x, interpret=interpret)


def fwht_large(x: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Two-pass radix-split FWHT for n > VMEM capacity:
    H_n = (H_a ⊗ I_b) (I_a ⊗ H_b) with n = a·b — pass 1 transforms the
    b axis of each (b, ·) panel; the transpose re-tiles; pass 2 transforms
    the a axis. Each pass is a VMEM-resident Pallas call."""
    n, d = x.shape
    lg = n.bit_length() - 1
    lb = min(lg, _FWHT_VMEM_MAX_N.bit_length() - 1)
    a, b = 1 << (lg - lb), 1 << lb
    # pass 1: I_a ⊗ H_b — reshape to (a, b, d), FWHT over b per slab
    y = x.reshape(a, b, d)
    y = jax.vmap(lambda s: fwht_pallas(s, interpret=interpret))(y)
    if a > 1:
        # pass 2: H_a ⊗ I_b — FWHT over the a axis: fold (b·d) into columns
        y = y.reshape(a, b * d)
        y = fwht_pallas(y, interpret=interpret)
        y = y.reshape(a, b, d)
    return y.reshape(n, d)


@functools.partial(jax.jit, static_argnames=("m", "use_pallas", "interpret",
                                             "compute_dtype"))
def sjlt_apply(A: jnp.ndarray, rows: jnp.ndarray, signs: jnp.ndarray, m: int,
               *, use_pallas: bool | None = None,
               interpret: bool | None = None,
               row_weights: jnp.ndarray | None = None,
               compute_dtype: str | None = None) -> jnp.ndarray:
    """S @ A for an s=1 SJLT given per-row targets/signs. ``row_weights``
    (n,) computes S·W^{1/2}·A by folding w^{1/2} into the signs;
    ``compute_dtype`` selects the bf16 dispatch-matmul / int8-codes stream
    (``kernels.precision``) on both backends."""
    use_pallas, interpret = _kernel_mode(use_pallas, interpret)
    signs = sjlt_fold_row_weights(signs, row_weights)
    if not use_pallas:
        return ref.sjlt_ref(A, rows, signs, m, compute_dtype=compute_dtype)
    return sjlt_pallas(A, rows, signs, m, interpret=interpret,
                       compute_dtype=compute_dtype)


@functools.partial(jax.jit, static_argnames=("m", "use_pallas", "interpret",
                                             "compute_dtype"))
def sjlt_apply_batched(A: jnp.ndarray, rows: jnp.ndarray, signs: jnp.ndarray,
                       m: int, *, use_pallas: bool | None = None,
                       interpret: bool | None = None,
                       row_weights: jnp.ndarray | None = None,
                       compute_dtype: str | None = None) -> jnp.ndarray:
    """Batch of SJLT sketches (B, m, d); A per-problem (B, n, d) or shared
    (n, d) across the batch (one grid cell per problem × row-block on TPU).
    ``row_weights`` (B, n) folds per-problem w^{1/2} into the sign stream
    — the weighted matrix W^{1/2}A never exists; ``compute_dtype`` rides
    the same slot (``kernels.precision``)."""
    use_pallas, interpret = _kernel_mode(use_pallas, interpret)
    signs = sjlt_fold_row_weights(signs, row_weights)
    if not use_pallas:
        return ref.sjlt_ref_batched(A, rows, signs, m,
                                    compute_dtype=compute_dtype)
    return sjlt_pallas_batched(A, rows, signs, m, interpret=interpret,
                               compute_dtype=compute_dtype)


@functools.partial(jax.jit, static_argnames=("m", "chunk_cols", "use_pallas",
                                             "interpret", "compute_dtype"))
def gaussian_sa(A: jnp.ndarray, seeds: jnp.ndarray, m: int, *,
                chunk_cols: int | None = None,
                use_pallas: bool | None = None,
                interpret: bool | None = None,
                row_weights: jnp.ndarray | None = None,
                compute_dtype: str | None = None) -> jnp.ndarray:
    """Streamed Gaussian sketch S @ A (B, m, d) without materializing S:
    A (n, d) shared or (B, n, d) per-problem, seeds (B,) uint32 — the fused
    generate-and-multiply Pallas kernel on TPU, the chunked ``lax.scan``
    oracle elsewhere. Sketch entries are identical on both paths (the same
    counter hash); only matmul reduction order differs.

    ``row_weights`` (B, n) computes S·W^{1/2}·A with w^{1/2} scaling the
    generated S tiles inside the stream (DESIGN.md §8) — neither S nor
    W^{1/2}A is ever materialized. ``compute_dtype`` selects the bf16 tile
    stream / int8-codes path (``kernels.precision``); both backends share
    the same dtype simulation, so results match per mode."""
    use_pallas, interpret = _kernel_mode(use_pallas, interpret)
    if not use_pallas:
        return gaussian_sa_ref(A, seeds, m,
                               chunk_cols=chunk_cols or 2048,
                               row_weights=row_weights,
                               compute_dtype=compute_dtype)
    return gaussian_sa_pallas(A, seeds, m, chunk_cols=chunk_cols or 512,
                              interpret=interpret, row_weights=row_weights,
                              compute_dtype=compute_dtype)


def fwht_cols(X: jnp.ndarray, *, use_pallas: bool | None = None,
              interpret: bool | None = None,
              row_scale: jnp.ndarray | None = None,
              compute_dtype: str | None = None) -> jnp.ndarray:
    """FWHT along axis -2 of a batched (B, n, d) stack (n a power of two):
    one vmapped kernel call on TPU, the jnp butterfly elsewhere.
    ``row_scale`` (B, n) computes H·diag(s_b)·X_b per problem — the SRHT
    provider passes signs·w^{1/2} (× int8 dequantization scales) here so
    the sign-flip (and any GLM weighting) fuses into the transform's VMEM
    tile on the Pallas path. Non-fp32 ``compute_dtype`` returns the
    transformed stack in bf16 — the (B, n_pad, d) intermediate, the peak
    allocation of the SRHT provider, halves."""
    if row_scale is None:
        return jax.vmap(lambda x: fwht(x, use_pallas=use_pallas,
                                       interpret=interpret,
                                       compute_dtype=compute_dtype))(X)
    return jax.vmap(lambda x, s: fwht(x, use_pallas=use_pallas,
                                      interpret=interpret, row_scale=s,
                                      compute_dtype=compute_dtype)
                    )(X, row_scale)


def srht_sketch(A: jnp.ndarray, key: jax.Array, m: int, *,
                use_pallas: bool | None = None,
                interpret: bool | None = None,
                row_weights: jnp.ndarray | None = None,
                compute_dtype: str | None = None) -> jnp.ndarray:
    """Full SRHT sketch √(n_pad/m)·R·H·E·A using the FWHT kernel.
    ``row_weights`` (n,) sketches W^{1/2}A by folding w^{1/2} into the
    sign flip (one fused row scale, no weighted copy of A); non-fp32
    ``compute_dtype`` runs the butterflies in bf16 (int8 codes stream with
    dequantization scales folded into the same row scale) and returns the
    sampled rows in fp32.

    Row-sampling law: the m rows of H are sampled WITHOUT replacement
    (``jax.random.choice``, the classical SRHT — every row distinct while
    m ≤ n_pad), which has slightly better embedding constants at large
    m/n_pad. This deliberately differs from ``level_grams.SRHTProvider``,
    whose rows are i.i.d. uniform WITH replacement: the ladder needs a
    fixed row *stream* whose every prefix is a valid sample, and prefixes
    of a without-replacement draw are not exchangeable across levels.
    Both are unbiased (E[SᵀS] = I); tests/test_sharded.py pins the two
    laws."""
    name = canonical_compute_dtype(compute_dtype)
    n, d = A.shape
    n_pad = 1 << max(0, (n - 1).bit_length())
    k_sign, k_rows = jax.random.split(key)
    sign_dtype = A.dtype if name == "fp32" else jnp.float32
    signs = jax.random.rademacher(k_sign, (n,), dtype=sign_dtype)
    scale = signs if row_weights is None else signs * jnp.sqrt(
        row_weights).astype(sign_dtype)
    if name == "int8" and A.dtype != jnp.int8:
        from repro.dist.compress import quantize_rows

        A, a_scales = quantize_rows(A)
        scale = scale * a_scales
    X = A
    if n_pad != n:
        X = jnp.pad(X, ((0, n_pad - n), (0, 0)))
        scale = jnp.pad(scale, (0, n_pad - n))
    HX = fwht(X, use_pallas=use_pallas, interpret=interpret, row_scale=scale,
              compute_dtype=compute_dtype)
    rows = jax.random.choice(k_rows, n_pad, shape=(m,), replace=m > n_pad)
    out_dtype = A.dtype if name == "fp32" else jnp.float32
    return HX[rows].astype(out_dtype) * jnp.asarray(math.sqrt(1.0 / m),
                                                    out_dtype)
