"""Pallas TPU kernel: SJLT sketch as one-hot MXU matmuls.

The SJLT applies S (one signed non-zero per column) to A: a segment-sum
    (SA)[r, :] = Σ_{i : row(i)=r} sign(i) · A[i, :].
On CPU/GPU this is a scatter-add; scatters are hostile to the TPU (serialized
through the scalar unit). TPU adaptation (DESIGN.md §3): per row-block of A,
build the signed one-hot dispatch matrix on the fly from (rows, signs) via
``broadcasted_iota`` comparison and contract it with the A tile on the MXU:

    out += OneHot(rows_blk)ᵀ_signed (m × br) @ A_blk (br × d).

The grid walks row blocks sequentially; the output block is revisited
(index_map constant) and accumulated in place — the standard Pallas
accumulator pattern. Dense systolic work replaces data-dependent scatter:
bandwidth-bound instead of latency-bound.

Batched layout (DESIGN.md §6): ``sjlt_pallas_batched`` puts a leading
problem axis on the grid — grid (B, n/br), one dispatch-matmul cell per
(problem, row-block). The problem axis is the outer (slowest) grid
dimension, so each problem's output block sees its row-blocks sequentially
and the same revisited-accumulator pattern applies per problem. The data
matrix may be per-problem (B, n, d) or shared (n, d) across the batch
(λ-sweep / multi-tenant serving); in the shared case the A tile is fetched
once per row-block index by the pipeline, not once per problem. The
single-problem ``sjlt_pallas`` is this kernel with B = 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .precision import canonical_compute_dtype, contract_dtype, fp32_precision


def fold_row_weights(signs: jnp.ndarray,
                     row_weights: jnp.ndarray | None) -> jnp.ndarray:
    """Weighted SJLT = S·diag(w^{1/2}): the sketch has one signed non-zero
    per column, so scaling column i by w_i^{1/2} is exactly scaling its
    sign — an O(n) elementwise fold on the (…, n) sign stream, never an
    (n, d) weighted copy of A (DESIGN.md §8)."""
    if row_weights is None:
        return signs
    return signs * jnp.sqrt(row_weights).astype(signs.dtype)


def fold_stream(A: jnp.ndarray, signs: jnp.ndarray,
                compute_dtype: str | None):
    """The SJLT's compute-dtype prep (``kernels.precision``), shared by the
    Pallas wrappers and the segment-sum oracle: on the int8 path A is
    quantized per row and the dequantization scales fold into the sign
    stream — exactly the ``fold_row_weights`` algebra, because the sketch
    has one signed non-zero per column, so S·diag(s)·codes scales sign i by
    s_i. Returns (A_stream, signs, contract dtype, out dtype)."""
    name = canonical_compute_dtype(compute_dtype)
    ct = contract_dtype(name)
    if name == "int8" and A.dtype != jnp.int8:
        from repro.dist.compress import quantize_rows

        codes, a_scales = quantize_rows(A)
        if a_scales.ndim < signs.ndim:        # shared A under batched signs
            a_scales = a_scales[None, :]
        signs = signs * a_scales
        A = codes
    out_dtype = jnp.float32 if (name != "fp32" or A.dtype == jnp.int8
                                ) else A.dtype
    return A, signs, ct, out_dtype


def sjlt_pallas(
    A: jnp.ndarray,
    rows: jnp.ndarray,
    signs: jnp.ndarray,
    m: int,
    *,
    block_rows: int = 256,
    interpret: bool = False,
    row_weights: jnp.ndarray | None = None,
    compute_dtype: str | None = None,
) -> jnp.ndarray:
    """S @ A for an s=1 SJLT. A: (n, d); rows/signs: (n,). Returns (m, d):
    the batched kernel with one problem. ``row_weights`` (n,) computes
    S·W^{1/2}·A by folding w^{1/2} into the sign stream
    (``fold_row_weights``); ``compute_dtype`` runs the dispatch-matmul in
    bf16 / streams int8 codes (``fold_stream``)."""
    weights = None if row_weights is None else row_weights[None]
    return sjlt_pallas_batched(A, rows[None], signs[None], m,
                               block_rows=block_rows, interpret=interpret,
                               row_weights=weights,
                               compute_dtype=compute_dtype)[0]


def _sjlt_kernel(rows_ref, signs_ref, a_ref, o_ref, *, m: int, ct):
    j = pl.program_id(1)            # row-block index (inner grid dim)
    rows = rows_ref[0]              # (1, br) this problem's targets
    signs = signs_ref[0]            # (1, br) ±1/√s (× w^{1/2} / int8 scales)
    a = a_ref[...]                  # (br, d) or (1, br, d) per-problem
    if a.ndim == 3:
        a = a[0]
    br = a.shape[0]
    # signed one-hot dispatch (m, br) built in VMEM; ct is the contract
    # dtype (fp32/bf16) — bf16 folds the sign stream into the MXU's native
    # mixed mode, fp32 accumulation via preferred_element_type either way
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (m, br), 0)
    onehot = jnp.where(row_ids == rows, signs, 0.0).astype(ct)
    acc = jnp.dot(onehot, a.astype(ct), precision=fp32_precision(ct),
                  preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        o_ref[0, ...] = acc.astype(o_ref.dtype)

    @pl.when(j > 0)
    def _acc():
        o_ref[0, ...] = (o_ref[0, ...].astype(jnp.float32) + acc).astype(
            o_ref.dtype
        )


def vmem_bytes(m: int, br: int, d: int, itemsize: int) -> int:
    """Scoped VMEM the kernel asks for: double-buffered A tile, (m, d) fp32
    accumulator and (1, br) row/sign blocks (padded to 8 sublanes), plus
    the (m, br) one-hot and its compare mask."""
    return (2 * br * d * itemsize + 2 * m * d * 4 + 4 * 8 * br * 4
            + 3 * m * br * 4 + (2 << 20))


def sjlt_pallas_batched(
    A: jnp.ndarray,
    rows: jnp.ndarray,
    signs: jnp.ndarray,
    m: int,
    *,
    block_rows: int = 256,
    interpret: bool = False,
    row_weights: jnp.ndarray | None = None,
    compute_dtype: str | None = None,
) -> jnp.ndarray:
    """Batch of s=1 SJLT sketches: one dispatch-matmul grid cell per
    (problem, row-block). A: (B, n, d) per-problem or (n, d) shared;
    rows/signs: (B, n). Returns (B, m, d). ``row_weights`` (B, n) folds
    per-problem w^{1/2} into the sign stream (``fold_row_weights``) — the
    shared-A fast path survives per-problem weights because the weight
    lives in the per-problem sketch, not in A. ``compute_dtype``
    (``kernels.precision``): bf16 dispatch-matmuls, or int8 A codes with
    the per-row dequantization scales folded into the sign stream
    (``fold_stream``) — the shared-A fast path survives quantization for
    the same reason it survives weights.

    The problem axis is the outer grid dimension so each problem's output
    block accumulates over its row-blocks. Rows and signs ride as (B, 1, n)
    views with (1, 1, block_rows) blocks, which the TPU tiling accepts.
    VMEM per step (``vmem_bytes``): br·d (A tile) + m·br (one-hot) + m·d
    (accumulator), double-buffered — the v5e compiler needs 4 MiB at
    m = 512, d = 256.
    """
    signs = fold_row_weights(signs, row_weights)
    A, signs, ct, out_dtype = fold_stream(A, signs, compute_dtype)
    B, n = rows.shape
    shared = A.ndim == 2
    d = A.shape[-1]
    if A.shape[-2] != n:
        raise ValueError(f"A rows {A.shape[-2]} != sketch columns {n}")
    if n % block_rows:
        pad = (-n) % block_rows
        pad_a = ((0, pad), (0, 0)) if shared else ((0, 0), (0, pad), (0, 0))
        A = jnp.pad(A, pad_a)
        rows = jnp.pad(rows, ((0, 0), (0, pad)), constant_values=m)
        signs = jnp.pad(signs, ((0, 0), (0, pad)))
        n = A.shape[-2]
    a_spec = (
        pl.BlockSpec((block_rows, d), lambda b, j: (j, 0))
        if shared
        else pl.BlockSpec((1, block_rows, d), lambda b, j: (b, j, 0))
    )
    stream_spec = pl.BlockSpec((1, 1, block_rows), lambda b, j: (b, 0, j))
    out = pl.pallas_call(
        functools.partial(_sjlt_kernel, m=m, ct=ct),
        grid=(B, n // block_rows),
        in_specs=[stream_spec, stream_spec, a_spec],
        out_specs=pl.BlockSpec((1, m, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, m, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(m, block_rows, d,
                                        A.dtype.itemsize)),
        interpret=interpret,
    )(rows.astype(jnp.int32).reshape(B, 1, n),
      signs.astype(jnp.float32).reshape(B, 1, n), A)
    return out
