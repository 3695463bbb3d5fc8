"""Hypothesis property tests on kernel/sketch invariants.

Kept in their own module behind ``pytest.importorskip`` so the suite
degrades gracefully where the optional dev dependency is absent
(``pip install -e .[dev]`` provides it); the deterministic oracle tests
live in test_kernels.py / test_embedding.py and always run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import fwht, make_sketch  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.fwht import fwht_pallas  # noqa: E402


@settings(max_examples=10, deadline=None)
@given(
    lg_n=st.integers(min_value=3, max_value=10),
    d=st.integers(min_value=1, max_value=16),
    seed=st.integers(min_value=0, max_value=2**30),
)
def test_fwht_kernel_property(lg_n, d, seed):
    n = 1 << lg_n
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    got = fwht_pallas(x, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.fwht_ref(x)),
                               rtol=1e-4, atol=1e-4)
    # Parseval: ‖Hx‖² = n‖x‖²
    np.testing.assert_allclose(float(jnp.sum(got**2)),
                               n * float(jnp.sum(x**2)), rtol=1e-3)


@settings(max_examples=20, deadline=None)
@given(
    lg_n=st.integers(min_value=1, max_value=9),
    d=st.integers(min_value=1, max_value=8),
    seed=st.integers(min_value=0, max_value=2**30),
)
def test_fwht_involution_property(lg_n, d, seed):
    """H(Hx) = n·x — the Hadamard transform is an involution up to n.
    Entries of H(Hx) are sums of n terms of size ~√n over 2·log₂n fp32
    stages, so the absolute error grows like ε·n·log₂n."""
    n = 1 << lg_n
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    hx = fwht(x, axis=0)
    hhx = fwht(hx, axis=0)
    np.testing.assert_allclose(np.asarray(hhx), n * np.asarray(x),
                               rtol=1e-4, atol=1e-6 * n * lg_n)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(min_value=8, max_value=200),
    m=st.integers(min_value=1, max_value=64),
    seed=st.integers(min_value=0, max_value=2**30),
)
def test_sjlt_column_norms(n, m, seed):
    """Every SJLT column has exactly s=1 entry of magnitude 1."""
    S = make_sketch("sjlt", m, n, jax.random.PRNGKey(seed)).dense()
    S = np.asarray(S)
    col_counts = (np.abs(S) > 0).sum(axis=0)
    np.testing.assert_array_equal(col_counts, np.ones(n))
    np.testing.assert_allclose(np.abs(S).sum(axis=0), np.ones(n), rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**30))
def test_sketch_linearity(seed):
    """S(aX + bY) = a·SX + b·SY for all sketch kinds."""
    n, d, m = 64, 8, 32
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    X = jax.random.normal(k1, (n, d))
    Y = jax.random.normal(k2, (n, d))
    for kind in ["gaussian", "srht", "sjlt"]:
        sk = make_sketch(kind, m, n, jax.random.PRNGKey(seed // 2))
        lhs = sk.apply(2.0 * X - 3.0 * Y)
        rhs = 2.0 * sk.apply(X) - 3.0 * sk.apply(Y)
        np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                                   rtol=1e-4, atol=1e-4)
