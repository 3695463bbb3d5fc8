"""Pallas TPU kernel: Fast Walsh–Hadamard transform (the SRHT hot spot).

The paper's SRHT sketch S·A = √(n/m)·R·H·E·A is dominated by the FWHT
H·(E·A) over the n-dimension of A (cost O(n·d·log n)). On CPU/GPU this is a
recursive butterfly; TPU-native design (DESIGN.md §3):

* A is processed in column tiles: a (n, bc) tile of the sign-flipped matrix
  lives in VMEM (BlockSpec over the d axis), padded so n is a power of two.
* All log₂(n) butterfly stages run *inside one kernel invocation* — no HBM
  round-trips between stages (a CPU implementation is memory-bound
  precisely because each stage streams n·d elements; fusing stages in VMEM
  turns log n passes into one).
* The stages run in place on the output block, ``_ROWS`` rows at a time:
  one pass applies every stage with h < ``_ROWS`` to each row chunk as a
  value (reshape/concat butterflies), then each wider stage pairs chunk
  (r, r+h) in place. Live temporaries are a few chunks, not copies of the
  whole (n, bc) tile: at n = 16384 (fp32) the v5e compiler needs 96 MiB of
  scoped VMEM for a whole-tile value butterfly and 33 MiB for this one, of
  which 32 MiB are the pipeline's double-buffered in and out blocks.
* For n too large for VMEM, the radix split H_n = (H_a ⊗ I_b)·(I_a ⊗ H_b)
  in ``ops.fwht_large`` runs two kernel passes with a transpose between,
  each pass transforming a VMEM-resident axis.

Grid: (d / bc,) — one program per column tile; row axis is not tiled
(the butterfly couples all n rows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 512   # row chunk of the in-place butterfly


def _butterfly(x: jnp.ndarray) -> jnp.ndarray:
    """All stages of an (r, bc) value's FWHT along axis 0."""
    r = x.shape[0]
    h = 1
    while h < r:
        x = x.reshape(r // (2 * h), 2, h, x.shape[-1])
        a = x[:, 0]
        b = x[:, 1]
        x = jnp.concatenate([a + b, a - b], axis=1)
        h *= 2
    return x.reshape(r, x.shape[-1])


def _fwht_kernel(*refs, n: int, scaled: bool):
    """One column tile: x_ref (n, bc) → o_ref, all stages in VMEM. With
    ``scaled`` the first ref is a (1, n) row scale (SRHT signs, optionally
    folded with GLM weights w^{1/2}) applied to each chunk before its first
    stage — the scaled matrix diag(s)·x never round-trips HBM."""
    s_ref, x_ref, o_ref = refs if scaled else (None, *refs)
    r = min(n, _ROWS)

    def small(i, carry):
        # one chunk (n ≤ _ROWS) slices statically: a lane offset below 128
        # must be a compile-time constant
        lo = 0 if n == r else pl.multiple_of(i * r, r)
        x = x_ref[pl.ds(lo, r), :]
        if scaled:
            x = x * jnp.transpose(s_ref[:, pl.ds(lo, r)])
        o_ref[pl.ds(lo, r), :] = _butterfly(x)
        return carry

    jax.lax.fori_loop(0, n // r, small, 0)
    h = r
    while h < n:
        def wide(i, carry, h=h):
            # pair i: chunk k of group g, rows [lo, lo + r) with [lo + h, …)
            g, k = i // (h // r), i % (h // r)
            lo = pl.multiple_of(g * 2 * h + k * r, r)
            hi = pl.multiple_of(lo + h, r)
            a = o_ref[pl.ds(lo, r), :]
            b = o_ref[pl.ds(hi, r), :]
            o_ref[pl.ds(lo, r), :] = a + b
            o_ref[pl.ds(hi, r), :] = a - b
            return carry

        jax.lax.fori_loop(0, n // (2 * r), wide, 0)
        h *= 2


def vmem_bytes(n: int, bc: int, itemsize: int, scaled: bool) -> int:
    """Scoped VMEM the kernel asks for: double-buffered in and out (n, bc)
    blocks, the (1, n) scale block padded to 8 sublanes, and headroom for
    the chunk butterfly's temporaries (bf16 relayouts through fp32)."""
    blocks = 4 * n * bc * itemsize
    scale = 2 * 8 * n * 4 if scaled else 0
    return blocks + scale + 32 * min(n, _ROWS) * bc * 4 + (1 << 20)


def fwht_pallas(
    x: jnp.ndarray,
    *,
    block_cols: int = 128,
    interpret: bool = False,
    row_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Unnormalized FWHT along axis 0 of x (n, d); n must be a power of 2.
    ``row_scale`` (n,) fuses H·diag(s)·x in one kernel (see
    ``_fwht_kernel``); it rides as a (1, n) block, which keeps the tiling
    rule under ``vmap`` (a (B, 1, n) array with a (1, n) block).

    VMEM budget (``vmem_bytes``): 4 · n · block_cols · itemsize for the
    pipeline's blocks plus chunk temporaries — 42 MiB asked at n = 16384
    fp32, where the v5e compiler needs 33 MiB (19 MiB in bf16);
    ``ops.fwht_large`` handles n beyond that.
    """
    n, d = x.shape
    if n & (n - 1):
        raise ValueError(f"n={n} must be a power of 2")
    bc = min(block_cols, d)
    pad = (-d) % bc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    dp = x.shape[1]
    scaled = row_scale is not None
    in_specs = [pl.BlockSpec((n, bc), lambda j: (0, j))]
    args = (x,)
    if scaled:
        in_specs = [pl.BlockSpec((1, n), lambda j: (0, 0))] + in_specs
        args = (row_scale.astype(x.dtype).reshape(1, n), x)
    out = pl.pallas_call(
        functools.partial(_fwht_kernel, n=n, scaled=scaled),
        grid=(dp // bc,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((n, bc), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n, dp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes(n, bc, x.dtype.itemsize, scaled)),
        interpret=interpret,
    )(*args)
    return out[:, :d] if pad else out
