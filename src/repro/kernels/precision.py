"""The compute-dtype axis of the one-touch sketch passes (DESIGN.md §10).

The adaptive ladder only needs the sketched Gram to be a *spectral
approximation* of the Hessian — the doubling controller absorbs
constant-factor sketch error by design, and preconditioner-reuse analyses
(arXiv 1911.02675, 2006.05874) show PCG iteration counts are insensitive
to modest perturbations of H_S. That headroom is what a reduced-precision
*stream* spends: the MXU-bound sketch→Gram contractions run at twice the
fp32 throughput in bf16 and the streamed operands halve (bf16) or quarter
(int8) their bandwidth, while everything the certificates depend on —
Gram accumulation, Cholesky factors, residuals, δ̃ — stays fp32.

Three named modes, plumbed end-to-end as a static string:

* ``"fp32"`` (default) — the existing bit-exact path; every wrapper with
  ``compute_dtype=None`` or ``"fp32"`` produces byte-identical results to
  the pre-dtype-axis code.
* ``"bf16"`` — sketch operands (generated S tiles, SJLT sign streams,
  FWHT butterfly tiles, A chunks) are cast to bfloat16 *in-register* and
  contracted with ``preferred_element_type=float32``: element products are
  bf16-rounded, accumulation is exact fp32 — the MXU's native mixed mode.
* ``"int8"`` — quantized-feature serving: A is quantized per ROW with
  symmetric int8 scales (Â = diag(s)·codes, |Â−A| ≤ s/2 entrywise), the
  int8 codes are what streams, and each family folds the dequantization
  scales into the per-row scale slot it already owns for GLM weights
  (generated-tile column scaling / sign stream / fused FWHT row scale) —
  dequantization happens in-register, never as an (n, d) float copy.
  Codes lie in [−127, 127] so their bf16 cast is exact and the contraction
  rides the same bf16×bf16→fp32 mode.

The canonical helpers here are shared by the kernels, their jnp oracles
and the level-Gram providers, so the tolerance model is identical on every
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

COMPUTE_DTYPES = ("fp32", "bf16", "int8")


def canonical_compute_dtype(compute_dtype: str | None) -> str:
    """Validate and canonicalize (None → "fp32")."""
    name = compute_dtype or "fp32"
    if name not in COMPUTE_DTYPES:
        raise ValueError(
            f"compute_dtype must be one of {COMPUTE_DTYPES}, "
            f"got {compute_dtype!r}")
    return name


def contract_dtype(compute_dtype: str | None):
    """The dtype sketch operands are cast to before the MXU contraction
    (accumulation is always fp32 via ``preferred_element_type``)."""
    return (jnp.float32 if canonical_compute_dtype(compute_dtype) == "fp32"
            else jnp.bfloat16)


def fp32_precision(ct):
    """The ``precision=`` of a contraction in dtype ``ct``: full fp32
    passes for fp32 operands (a TPU's default runs an fp32 dot as one bf16
    pass), the MXU's native mode for bf16 — stated explicitly, so that an
    enclosing ``fp32_contractions`` scope does not reach it."""
    return (jax.lax.Precision.HIGHEST if ct == jnp.float32
            else jax.lax.Precision.DEFAULT)


def fp32_contractions(fn):
    """Trace (or run) ``fn`` with fp32 as the default matmul precision.

    The engine's entry points wear this under their ``jax.jit``: every
    contraction the certificates depend on — the hvp, the exact and ladder
    Grams, the factorizations' matmuls, residuals and δ̃ — then runs at
    fp32 on a TPU, where an fp32 dot would otherwise be one bf16 pass
    (~4e-3 relative, far above the service's δ̃ tolerance). Sketch-stream
    contractions that state their own precision (``fp32_precision``) keep
    it. CPU backends compute fp32 dots in fp32 either way, so results there
    are unchanged bit for bit."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)
    return scoped


def stream_itemsize(compute_dtype: str | None) -> int:
    """Bytes per streamed A element (the bandwidth axis of the win)."""
    return {"fp32": 4, "bf16": 2, "int8": 1}[
        canonical_compute_dtype(compute_dtype)]
