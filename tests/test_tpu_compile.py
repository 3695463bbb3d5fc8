"""The main path's Pallas kernels compile for a TPU v5e at service shapes.

Interpret-mode tests (test_kernels.py) cannot see what the chip's compiler
refuses: blocks that break the (8, 128) tiling rule, layouts that disagree
with XLA's, or more scoped VMEM than a kernel may use. These tests compile
each kernel for a described ``v5e:2x2`` topology — no chip is attached or
needed — with explicit ``use_pallas=True, interpret=False``, and assert the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture (never at import), because only
one process may load the TPU library; the persistent compilation cache is
off around these compiles, since an entry written for a described chip
cannot be read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.gaussian_gram import gaussian_sa_pallas
from repro.kernels.sjlt import sjlt_pallas_batched

B, N, D, M = 16, 4096, 256, 512      # the n=4096, d=256 service class


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, U32, I32 = jnp.float32, jnp.uint32, jnp.int32


@pytest.mark.parametrize("variant", ["shared", "per_problem", "weighted",
                                     "bf16"])
def test_gaussian_sketch_kernel_compiles(one_chip, variant):
    a_shape = (N, D) if variant == "shared" else (B, N, D)
    shapes = [(a_shape, F32), ((B,), U32)]
    if variant == "weighted":
        shapes.append(((B, N), F32))

    def fn(A, seeds, *w):
        return gaussian_sa_pallas(
            A, seeds, M, interpret=False,
            row_weights=w[0] if w else None,
            compute_dtype="bf16" if variant == "bf16" else None)

    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_sjlt_batched_kernel_compiles(one_chip):
    def fn(A, rows, signs):
        return sjlt_pallas_batched(A, rows, signs, M, interpret=False)

    text = _compiled_text(fn, one_chip, ((B, N, D), F32), ((B, N), I32),
                          ((B, N), F32))
    assert "tpu_custom_call" in text


def test_fwht_cols_with_row_scale_compiles(one_chip):
    n = 16384                         # the n=16384, d=256 srht class

    def fn(X, s):
        return ops.fwht_cols(X, use_pallas=True, interpret=False, row_scale=s)

    text = _compiled_text(fn, one_chip, ((B, n, D), F32), ((B, n), F32))
    assert "tpu_custom_call" in text


def test_fwht_large_compiles(one_chip):
    n = 65536                         # per-shard pass of the sharded class

    def fn(x):
        return ops.fwht_large(x, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, one_chip, ((n, D), F32))


def test_engine_compiles_in_dual_ladder_form(one_chip, monkeypatch):
    """The whole engine, as the library cell calls it (pcg, gaussian,
    matrix-free hvp, m_max = d/4, n = 4d), compiles for a v5e in the dual
    ladder form: the Pallas sketch kernel is in it, no d×d array is, and
    its scratch is below one primal (L, 1, d, d) table. The cell's own
    shape (n 16384, d 4096, m_max 1024) takes ≈ 40 s to compile here, so
    this is the same program at a quarter of each size (≈ 6 s)."""
    from repro.core.adaptive_padded import (doubling_ladder,
                                            padded_adaptive_solve_batched)
    from repro.core.quadratic import Quadratic

    n, d, m_max = 4096, 1024, 256
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    sds = lambda s, dt=F32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    q = Quadratic(A=sds((n, d)), b=sds((1, d)), nu=sds((1,)),
                  lam_diag=sds((1, d)), batched=True)
    compiled = padded_adaptive_solve_batched.lower(
        q, sds((1, 2), U32), m_max=m_max, method="pcg", sketch="gaussian",
        max_iters=200, tol=1e-10, gram_hvp=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"{d},{d}]" not in text
    primal_table = len(doubling_ladder(m_max)) * d * d * 4
    assert compiled.memory_analysis().temp_size_in_bytes < primal_table
