"""Kernel microbenchmarks: FWHT / SJLT wrappers vs jnp oracles on CPU
(wall-time here is the *oracle* path — the Pallas path is TPU-target and
is validated for semantics in interpret mode; see tests/test_kernels.py).
Reports us_per_call + achieved effective GB/s for the CPU oracle."""

from __future__ import annotations

import time

import jax

from repro.kernels import ref
from .common import emit


def _time(fn, *args, iters=5):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def run():
    rows = []
    for n, d in [(4096, 256), (16384, 512)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        f = jax.jit(ref.fwht_ref)
        dt = _time(f, x)
        nbytes = n * d * 4 * (n.bit_length() - 1)
        rows.append(dict(bench="fwht_ref", n=n, d=d,
                         us_per_call=round(dt * 1e6, 1),
                         eff_gbps=round(nbytes / dt / 1e9, 2)))
    for n, d, m in [(16384, 512, 1024), (65536, 256, 2048)]:
        A = jax.random.normal(jax.random.PRNGKey(1), (n, d))
        rows_i = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, m)
        signs = jax.random.rademacher(jax.random.PRNGKey(3), (n,),
                                      dtype=A.dtype)
        fn = jax.jit(lambda A, r, s: ref.sjlt_ref(A, r, s, m))
        dt = _time(fn, A, rows_i, signs)
        rows.append(dict(bench="sjlt_ref", n=n, d=d, m=m,
                         us_per_call=round(dt * 1e6, 1),
                         eff_gbps=round(n * d * 4 / dt / 1e9, 2)))
    for r in rows:
        emit(r)
    return rows


if __name__ == "__main__":
    run()
