"""The one traffic generator: reads a mix's parameters and makes its
requests, their order and their arrival times.

A mix fixes the *set* of problems, sizes, ν and spectra, and the set of
inter-arrival gaps; ``--seed`` makes the matrices and shuffles the order.
So every seed does the same work in another order, every seed compiles
the same shapes, and two runs of one seed are identical.

Problem k of a pool is ``A = (Z/√n) · diag(σ) · Vᵀ`` with Z Gaussian,
σ_j = rate^j (rate 1 is a plain Gaussian matrix) and V a random orthogonal
matrix, plus a Gaussian target y: the synthetic family of arXiv:2104.14101
§6. All of a pool is made on the device in one jitted call.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# fixed stream that lays the grids of sizes, ν and rates over the pool
# slots: part of the mix, the same for every seed
_LAYOUT_SEED = 2104_14101


@dataclasses.dataclass(frozen=True)
class Problem:
    n: int
    d: int
    nu: float
    rate: float


def _span(v) -> tuple[float, float]:
    return (float(v[0]), float(v[1])) if isinstance(v, (list, tuple)) \
        else (float(v), float(v))


def _grid(lo: float, hi: float, count: int, *, integer: bool,
          perm: np.ndarray) -> list:
    """``count`` quantiles of U[lo, hi], laid out over slots by ``perm``."""
    q = (np.arange(count) + 0.5) / count
    vals = lo + q * (hi - lo + (1 if integer else 0))
    vals = np.floor(vals).astype(int) if integer else vals
    vals = np.minimum(vals, hi) if integer else vals
    return [vals[i].item() for i in perm]


def pool(mix: dict, base: dict | None = None) -> list[Problem]:
    """The mix's pool of problems. Keys of the mix (``n``, ``d``, ``nu``,
    ``spectrum_rate``: a number or a [lo, hi] range) override ``base``
    (a configuration's fixed problem)."""
    spec = dict(base or {})
    spec.update({k: mix[k] for k in ("n", "d", "nu", "spectrum_rate")
                 if k in mix})
    count = int(mix["pool"])
    rng = np.random.default_rng(_LAYOUT_SEED)
    n = _grid(*_span(spec["n"]), count, integer=True,
              perm=rng.permutation(count))
    d = _grid(*_span(spec["d"]), count, integer=True,
              perm=rng.permutation(count))
    nu = _grid(*_span(spec["nu"]), count, integer=False,
               perm=rng.permutation(count))
    rate = _grid(*_span(spec.get("spectrum_rate", 1.0)), count,
                 integer=False, perm=rng.permutation(count))
    return [Problem(int(a), int(b), float(c), float(e))
            for a, b, c, e in zip(n, d, nu, rate)]


def order(mix: dict, seed: int):
    """Pool index of each request, without end: the pool in a fresh
    seed-shuffled order on each pass (``"order": "cyclic"`` keeps k mod P)."""
    size = int(mix["pool"])
    rng = np.random.default_rng([seed, 1])
    while True:
        if mix.get("order") == "cyclic":
            yield from range(size)
        else:
            yield from (int(i) for i in rng.permutation(size))


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop at
    ``rate_per_s``: the gaps are the quantiles of the exponential
    distribution, so every seed sends the same number of requests with the
    same gaps, shuffled by the seed."""
    rate = float(mix["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    rng = np.random.default_rng([seed, 2])
    return np.cumsum(rng.permutation(gaps))


def jax_key(seed: int, salt: int = 0):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed) % (1 << 64)
    hi, lo = divmod(seed, 1 << 32)
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(lo), hi), salt)


@partial(jax.jit, static_argnames=("shapes",))
def _make(key, shapes):
    hi = jax.lax.Precision.HIGHEST
    out = []
    for i, (n, d, rate) in enumerate(shapes):
        kz, kv, ky = jax.random.split(jax.random.fold_in(key, i), 3)
        A = jax.random.normal(kz, (n, d), jnp.float32) / math.sqrt(n)
        if rate != 1.0:
            # a Gaussian matrix is rotation-invariant: V only matters once
            # the spectrum is shaped
            sv = rate ** jnp.arange(d, dtype=jnp.float32)
            V, _ = jnp.linalg.qr(jax.random.normal(kv, (d, d), jnp.float32))
            A = jnp.matmul(A * sv, V.T, precision=hi)
        y = jax.random.normal(ky, (n,), jnp.float32)
        out.append((A, y))
    return out


def make(problems: list[Problem], seed: int):
    """[(A, y)] of the pool on the default device, from the seed, in one
    jitted call."""
    return _make(jax_key(seed, 7), tuple((p.n, p.d, p.rate) for p in problems))
