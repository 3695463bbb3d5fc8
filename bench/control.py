"""The control of the comparison that decides ``correct``: the reference
direct solve put in the program's place and computed on the device one
precision below the configuration's.

The configurations state float32 with every contraction at
``Precision.HIGHEST``. The nearest precision below is ``HIGH``: three
bfloat16 passes, a_hi·b_hi + a_hi·b_lo + a_lo·b_hi, summed in float32.
The split is spelled out on bfloat16-typed operands, so that it means the
same on every backend and no compiler can fold it back into float32: hi
is a's float32 bits with the low 16 cleared (exact in bfloat16), lo is
the rest rounded to bfloat16, and each pass is a bfloat16 dot that
accumulates in float32. The normal equations are then solved by a
float32 Cholesky.

``ControlCell`` is the library driver with this solve in place of the
engine call, so the control runs through the same window and the same
comparison (``run.execute``) as the program, at the cell's own size:

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 5

prints each seed's result line, whose ``correct`` has to come out false.
The benchmark's own runs never run it; ``bench/tests/test_control.py``
keeps it at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def _split(a):
    """(hi, lo) bfloat16 parts of a float32 array: hi + lo ≈ a to 2⁻¹⁶."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi32 = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)
    return hi32.astype(jnp.bfloat16), (a - hi32).astype(jnp.bfloat16)


def high_dot(a, b):
    """aᵀb at HIGH: three bfloat16 passes summed in float32."""
    import jax
    import jax.numpy as jnp

    def dot(u, v):
        return jax.lax.dot_general(u, v, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def _solve(A, y, nu):
    import jax
    import jax.numpy as jnp

    H = high_dot(A, A) + (nu * nu) * jnp.eye(A.shape[1], dtype=A.dtype)
    rhs = high_dot(A, y[:, None])[:, 0]
    L = jnp.linalg.cholesky(H)
    z = jax.scipy.linalg.solve_triangular(L, rhs, lower=True)
    return jax.scipy.linalg.solve_triangular(L.T, z, lower=False)


def control_cell(base):
    """The driver class ``base`` with the control solve in place of the
    program's: same build, pool, order, window and answers."""
    import jax
    import jax.numpy as jnp

    from repro.core.status import SolveStatus

    solve = jax.jit(_solve)

    class ControlCell(base):
        def _solve(self, k, p):
            A, y = self.data[p]
            x = solve(A, y, jnp.float32(self.problems[p].nu))
            # reported as certified, so that only the answer's error can
            # fail the comparison
            zero = jnp.float32(0)
            return x, {"status": jnp.int32(SolveStatus.OK), "iters": zero,
                       "m_final": zero, "doublings": zero, "dtilde": zero}

    return ControlCell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import run
    import spec

    sys.path.insert(0, str(run.REPO / "src"))
    import jax

    cache = run.configure_cache(jax)
    bm = spec.load_benchmark()
    wl = spec.workload(bm, args.workload)
    cfg = spec.config(bm, wl["config"])
    mix = spec.traffic(wl["traffic"])
    metrics = spec.metrics_for(bm, wl["name"], trace=False)
    import cell

    limits = cell.limits(wl["name"])
    devices = jax.devices()
    peaks = spec.peaks(devices[0].device_kind)
    make = control_cell(spec.load_plugin("drivers", cfg["entry"]).Cell)
    for seed in args.seeds:
        result, lines = run.execute(wl, cfg, mix, metrics, limits, seed,
                                    args.seconds, False, devices, peaks,
                                    cache, make_cell=make)
        for line in lines:
            print(line, file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps({"control": wl["name"], "seed": seed, **result}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
