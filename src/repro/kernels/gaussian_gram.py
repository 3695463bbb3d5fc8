"""Pallas TPU kernel: streaming fused Gaussian sketch→(SA) with in-kernel PRNG.

The padded adaptive engine precomputes sketched Grams at every doubling-
ladder level. Materializing the Gaussian sketch S (B, m_max, n) in HBM and
pushing it through an einsum is memory-bound and allocates O(B·m_max·n) —
the opposite of the paper's O(n·d) sketch-pass accounting. This kernel
never materializes S: each grid cell *generates* its (m_max, chunk) tile of
S on the fly from a counter-based PRNG in VMEM and contracts it with the
matching A chunk on the MXU, accumulating SA (B, m_max, d) with the
standard revisited-output pattern (DESIGN.md §3). A is streamed exactly
once in n-chunks; live memory is O(B·m_max·d) ≪ O(B·m_max·n).

PRNG design: entries are a pure function of (problem seed, row, column) —
a murmur3-finalizer counter hash feeding Box–Muller — so

* the kernel and the chunked ``lax.scan`` oracle (``gaussian_sa_ref``, the
  CPU/GPU path) draw bit-identical sketch entries;
* numerics are *chunk-invariant by construction*: the oracle reduces the
  n axis at a fixed ``_MICRO``-column granularity in a fixed order, so any
  public chunk size produces bit-identical SA (tested);
* no backend-specific PRNG primitive is needed — the hash is plain uint32
  jnp arithmetic, so the same kernel body compiles on TPU Mosaic and runs
  under ``interpret=True`` on CPU.

Counters pack (row, col) as ``row·2^20 + col`` in uint32, which is
injective for n ≤ 2^20 columns and m_max ≤ 2^12 rows — far above any
sketch this engine builds (m_max is a few·d); asserted in the wrappers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .precision import canonical_compute_dtype, contract_dtype, fp32_precision

# Canonical micro-tile of the n axis: the oracle always reduces n in
# _MICRO-column steps so chunk size never changes numerics; the Pallas
# kernel requires chunk % _MICRO == 0 so its tiles see the same counters.
_MICRO = 256
_COL_BITS = 20                 # counters: row · 2^20 + col
MAX_N = 1 << _COL_BITS         # column capacity of the counter packing
MAX_M = 1 << (32 - _COL_BITS)  # row capacity

# numpy scalars (not jnp arrays): they inline as jaxpr literals, which a
# Pallas kernel body may close over — committed device arrays may not
_GOLD = np.uint32(0x9E3779B9)
_SEQ2 = np.uint32(0x7F4A7C15)
_MUL1 = np.uint32(0x85EBCA6B)
_MUL2 = np.uint32(0xC2B2AE35)


def _mix(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer: a bijective uint32 avalanche."""
    x = (x ^ (x >> 16)) * _MUL1
    x = (x ^ (x >> 13)) * _MUL2
    return x ^ (x >> 16)


def gaussian_tile(seed, row0, col0, shape) -> jnp.ndarray:
    """(shape) float32 tile of the seed's N(0,1) sketch at (row0, col0).

    Pure uint32 jnp arithmetic + Box–Muller, usable identically inside a
    Pallas kernel body and in plain jitted code. ``seed``/``row0``/``col0``
    may be traced scalars.
    """
    r = jnp.uint32(row0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jnp.uint32(col0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    ctr = (r << _COL_BITS) + c
    k = _mix(jnp.uint32(seed) ^ _GOLD)
    h1 = _mix(ctr ^ k)
    h2 = _mix(h1 + _SEQ2)
    # 24-bit mantissas; u1 offset into (0, 1) so log(u1) is finite
    # the 24-bit values convert through int32 (exact): Mosaic has no
    # uint32 → float32 conversion
    u1 = (h1 >> 8).astype(jnp.int32).astype(jnp.float32) * (
        1.0 / 16777216.0) + (0.5 / 16777216.0)
    u2 = (h2 >> 8).astype(jnp.int32).astype(jnp.float32) * (
        1.0 / 16777216.0)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(6.2831853071795864 * u2)


def _check_caps(n: int, m: int) -> None:
    if n > MAX_N or m > MAX_M:
        raise ValueError(
            f"counter packing supports n ≤ {MAX_N}, m ≤ {MAX_M}; "
            f"got n={n}, m={m}")


def gaussian_s_dense(seeds: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    """Materialize the full (B, m, n) sketch — the dense baseline/oracle.

    Entry [b, r, c] is exactly what the streaming kernel/oracle generate
    for problem b at (row r, column c)."""
    _check_caps(n, m)
    return jax.vmap(lambda s: gaussian_tile(s, 0, 0, (m, n)))(seeds)


# ---------------------------------------------------------------------------
# Chunked lax.scan oracle — the CPU/GPU streaming path
# ---------------------------------------------------------------------------

def resolve_stream(A: jnp.ndarray, B: int,
                   row_weights: jnp.ndarray | None,
                   compute_dtype: str | None):
    """The Gaussian family's compute-dtype prep, shared by the oracle, the
    Pallas wrapper and the dense provider (``kernels.precision``).

    Folds everything that scales the generated S tile's columns into ONE
    per-column fp32 scale: the GLM w^{1/2} (as before) and, on the int8
    path, the per-row dequantization scales of the quantized A — so the
    kernels dequantize in-register by construction, streaming int8 codes
    and multiplying diag(scales) into the tile they already generate.

    Returns (A_stream, scale (B, n) | None, contract dtype, out dtype).
    """
    name = canonical_compute_dtype(compute_dtype)
    ct = contract_dtype(name)
    scale = (None if row_weights is None
             else jnp.sqrt(row_weights.astype(jnp.float32)))
    if name == "int8" and A.dtype != jnp.int8:
        from repro.dist.compress import quantize_rows

        codes, a_scales = quantize_rows(A)
        if A.ndim == 2:                       # shared A: broadcast per problem
            a_scales = jnp.broadcast_to(a_scales[None, :], (B, A.shape[0]))
        scale = a_scales if scale is None else scale * a_scales
        A = codes
    out_dtype = jnp.float32 if (name != "fp32" or A.dtype == jnp.int8
                                ) else A.dtype
    return A, scale, ct, out_dtype


def gaussian_sa_ref(A: jnp.ndarray, seeds: jnp.ndarray, m: int, *,
                    chunk_cols: int = 2048,
                    row_weights: jnp.ndarray | None = None,
                    compute_dtype: str | None = None) -> jnp.ndarray:
    """Streamed S @ A without materializing S: (B, m, d) from A (n, d)
    shared or (B, n, d) per-problem and per-problem uint32 seeds (B,).

    ``lax.scan`` walks n-chunks of A; inside each step a ``fori_loop``
    reduces the chunk in fixed _MICRO-column micro-tiles, so the sequence
    of partial products — and therefore the result, bit-for-bit — is
    independent of ``chunk_cols`` (which only sets live-memory/pipelining
    granularity). Peak live sketch state is (B, m, _MICRO) + the (B, m, d)
    accumulator.

    ``row_weights`` (B, n): computes S·W^{1/2}·A by scaling the generated
    (B, m, _MICRO) S tile columns by w^{1/2} inside the stream — the
    weighted matrix W^{1/2}A never exists (DESIGN.md §8).

    ``compute_dtype`` (``kernels.precision``): ``"bf16"`` casts the scaled
    S micro-tile and the A micro-slice to bfloat16 before the contraction
    (``preferred_element_type=float32`` keeps the accumulator exact fp32);
    ``"int8"`` additionally streams per-row-quantized codes of A with the
    dequantization scales folded into the same per-column tile scale as
    the weights. The fixed-micro-tile reduction order is dtype-independent,
    so chunk invariance holds bit-for-bit PER dtype."""
    shared = A.ndim == 2
    n, d = A.shape[-2], A.shape[-1]
    B = seeds.shape[0]
    _check_caps(n, m)
    A, scale, ct, out_dtype = resolve_stream(A, B, row_weights, compute_dtype)
    k = max(1, -(-chunk_cols // _MICRO))      # micro-tiles per scan step
    k = min(k, -(-n // _MICRO))               # never pad n past one chunk
    chunk = k * _MICRO
    pad = (-n) % chunk
    if pad:
        # zero columns: their generated sketch entries multiply 0.0, and
        # acc + 0.0 is exact, so padding never changes the result
        A = jnp.pad(A, ((0, pad), (0, 0)) if shared
                    else ((0, 0), (0, pad), (0, 0)))
        if scale is not None:
            scale = jnp.pad(scale, ((0, 0), (0, pad)))
    steps = (n + pad) // chunk
    if shared:
        contract = lambda S, a: jnp.einsum(
            "bmc,cd->bmd", S, a, preferred_element_type=jnp.float32)
    else:
        contract = lambda S, a: jnp.einsum(
            "bmc,bcd->bmd", S, a, preferred_element_type=jnp.float32)

    def step(acc, c_idx):
        # A is sliced in place (no re-layout copy): the only live sketch
        # state is the (B, m, _MICRO) tile and the (B, m, d) accumulator
        def micro(i, acc):
            col0 = c_idx * chunk + i * _MICRO
            S = jax.vmap(lambda s: gaussian_tile(
                s, 0, col0.astype(jnp.uint32), (m, _MICRO)))(seeds)
            if scale is not None:
                s_mu = jax.lax.dynamic_slice_in_dim(
                    scale, col0, _MICRO, axis=1)
                S = S * s_mu[:, None, :]
            a_mu = jax.lax.dynamic_slice_in_dim(
                A, col0, _MICRO, axis=A.ndim - 2)
            return acc + contract(S.astype(ct), a_mu.astype(ct))

        return jax.lax.fori_loop(0, k, micro, acc), None

    acc0 = jnp.zeros((B, m, d), jnp.float32)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(steps))
    return acc.astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas kernel — grid (B, n/chunk), S tile generated in VMEM per cell
# ---------------------------------------------------------------------------

def _gauss_sa_kernel(*refs, m: int, chunk: int, ct, scaled: bool):
    """One (problem, chunk) cell. ``seed_ref`` is the whole (B,) seed table
    in SMEM (a (1,) VMEM block per problem breaks the rank-1 tiling rule).
    With ``scaled`` a (1, 1, chunk) block of the pre-folded fp32
    per-column factor — w^{1/2} (GLM weights), int8 dequantization scales,
    or their product (``resolve_stream``) — scales the generated S tile's
    columns in VMEM before the contraction: S·diag(s)·A fused, with
    neither S nor the scaled A ever in HBM; on the int8 path ``a`` holds
    codes that are dequantized in-register by this scale."""
    seed_ref, s_ref, a_ref, o_ref = refs if scaled else (refs[0], None,
                                                         *refs[1:])
    c = pl.program_id(1)
    seed = seed_ref[pl.program_id(0)]
    col0 = (c * chunk).astype(jnp.uint32)
    S = gaussian_tile(seed, 0, col0, (m, chunk))   # VMEM-only, never in HBM
    if scaled:
        S = S * s_ref[0].astype(jnp.float32)
    a = a_ref[...]
    if a.ndim == 3:
        a = a[0]
    # ct is the contract dtype (kernels.precision): fp32 or bf16. The cast
    # happens on the VMEM tile/chunk in-register; the MXU accumulates fp32
    # via preferred_element_type either way, and an fp32 contraction asks
    # for full fp32 passes.
    acc = jnp.dot(S.astype(ct), a.astype(ct),
                  precision=fp32_precision(ct),
                  preferred_element_type=jnp.float32)

    @pl.when(c == 0)
    def _init():
        o_ref[0, ...] = acc.astype(o_ref.dtype)

    @pl.when(c > 0)
    def _acc():
        o_ref[0, ...] = (o_ref[0, ...].astype(jnp.float32) + acc).astype(
            o_ref.dtype)


def vmem_bytes(m: int, chunk: int, d: int, itemsize: int) -> int:
    """Scoped VMEM the kernel asks for: double-buffered A chunk and (m, d)
    fp32 accumulator blocks, plus ~10 (m, chunk) 32-bit tiles for the
    counter hash and Box–Muller temporaries."""
    return (2 * chunk * d * itemsize + 2 * m * d * 4
            + 10 * m * chunk * 4 + (2 << 20))


def gaussian_sa_pallas(
    A: jnp.ndarray,
    seeds: jnp.ndarray,
    m: int,
    *,
    chunk_cols: int = 512,
    interpret: bool = False,
    row_weights: jnp.ndarray | None = None,
    compute_dtype: str | None = None,
) -> jnp.ndarray:
    """Fused generate-and-multiply Gaussian sketch: (B, m, d) from
    A (n, d) shared or (B, n, d) per-problem; seeds (B,) uint32.

    Grid (B, n/chunk): each cell generates its (m, chunk) S tile from the
    counter hash in VMEM and contracts it with the A chunk on the MXU;
    the output block is revisited over the chunk axis (accumulator
    pattern). VMEM per step (``vmem_bytes``): two A chunks, two (m, d)
    accumulators and the hash temporaries — 34 MiB asked at m = d = 1024,
    chunk = 512 (fp32), where the accumulator block alone is 4 MiB and the
    v5e compiler needs 20 MiB (7 MiB at m = 512, d = 256).
    Entries match ``gaussian_sa_ref`` / ``gaussian_s_dense`` bit-for-bit
    (same counter hash); the contraction differs only in reduction order.

    ``row_weights`` (B, n) switches to the scaled kernel: the S tile is
    scaled by w^{1/2} in VMEM (one extra (1, 1, chunk) block of a
    (B, 1, n) view per cell); W^{1/2}A never exists in HBM.

    ``compute_dtype`` (``kernels.precision``): ``"bf16"`` casts the S tile
    and A chunk to bfloat16 in-register for the MXU's bf16×bf16→fp32 mode
    (pass A already stored in bf16 to also halve the HBM stream — the cast
    composes, the one touch of A stays one touch); ``"int8"`` streams
    per-row int8 codes of A and folds the dequantization scales into the
    scaled kernel's per-column factor alongside any weights."""
    shared = A.ndim == 2
    n, d = A.shape[-2], A.shape[-1]
    B = seeds.shape[0]
    _check_caps(n, m)
    A, scale, ct, out_dtype = resolve_stream(A, B, row_weights, compute_dtype)
    chunk = max(_MICRO, (chunk_cols // _MICRO) * _MICRO)
    chunk = min(chunk, -(-n // _MICRO) * _MICRO)  # never pad past one chunk
    pad = (-n) % chunk
    if pad:
        A = jnp.pad(A, ((0, pad), (0, 0)) if shared
                    else ((0, 0), (0, pad), (0, 0)))
        if scale is not None:
            scale = jnp.pad(scale, ((0, 0), (0, pad)))
        n = n + pad
    scaled = scale is not None
    a_spec = (
        pl.BlockSpec((chunk, d), lambda b, c: (c, 0))
        if shared
        else pl.BlockSpec((1, chunk, d), lambda b, c: (b, c, 0))
    )
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), a_spec]
    args = [seeds.astype(jnp.uint32), A]
    if scaled:
        in_specs.insert(1, pl.BlockSpec((1, 1, chunk),
                                        lambda b, c: (b, 0, c)))
        args.insert(1, scale.astype(jnp.float32).reshape(B, 1, n))
    return pl.pallas_call(
        functools.partial(_gauss_sa_kernel, m=m, chunk=chunk, ct=ct,
                          scaled=scaled),
        grid=(B, n // chunk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, m, d), lambda b, c: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, m, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(m, chunk, d, A.dtype.itemsize)),
        interpret=interpret,
    )(*args)
