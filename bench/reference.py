"""The plain reference: a float64 direct solve on the host.

    x* = (AᵀA + ν²Λ)⁻¹ Aᵀy

It imports nothing of the program under test and takes nothing it made:
A and y are the benchmark's own data, copied to the host. The Gram is
accumulated over blocks of rows so that a 2¹⁷-row problem needs one block
of float64 at a time.
"""

from __future__ import annotations

import numpy as np

ROW_BLOCK = 16384


def ridge_ref(A, y, nu: float, lam_diag=None) -> np.ndarray:
    """float64 solve of (AᵀA + ν²Λ) x = Aᵀy."""
    A = np.asarray(A)
    y = np.asarray(y, np.float64)
    n, d = A.shape
    H = np.zeros((d, d), np.float64)
    rhs = np.zeros((d,), np.float64)
    for r0 in range(0, n, ROW_BLOCK):
        blk = np.asarray(A[r0:r0 + ROW_BLOCK], np.float64)
        H += blk.T @ blk
        rhs += blk.T @ y[r0:r0 + ROW_BLOCK]
    lam = np.ones(d) if lam_diag is None else np.asarray(lam_diag, np.float64)
    H[np.diag_indices(d)] += nu * nu * lam
    return np.linalg.solve(H, rhs)


def rel_err(x, ref: np.ndarray) -> float:
    """‖x − x*‖₂ / ‖x*‖₂ in float64."""
    return float(np.linalg.norm(np.asarray(x, np.float64) - ref)
                 / np.linalg.norm(ref))
