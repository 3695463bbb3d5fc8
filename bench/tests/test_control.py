"""The control of the comparison: the reference direct solve, computed one
precision below the configuration's (float32 at HIGH, three bfloat16
passes), put in the program's place, has to come out not correct where
the program is correct, through the same run (``run.execute``).

At a size a test run holds: n = 16384, d = 256, σ_j = 0.98^j, ν = 0.01
(cond(H) ≈ 7.5e3, the cell's is 1e4). The chip readings at the cell's own
size are in PERF.md."""

import jax
import jax.numpy as jnp
import numpy as np

import cell
import control
import run
import spec
import tiny

NAME = "lib-expdecay-16k"
PROBLEM = {"n": 16384, "d": 256, "spectrum_rate": 0.98, "nu": 0.01}
CPU_PEAKS = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}


def _execute(seed, make_cell=None):
    wl, cfg, mix = tiny.cell(NAME)
    cfg["problem"] = PROBLEM
    cfg["solver"] = {**cfg["solver"], "m_max": 256}
    metrics = spec.metrics_for(spec.load_benchmark(), NAME, trace=False)
    return run.execute(wl, cfg, mix, metrics, cell.limits(NAME), seed, 0.5,
                       False, jax.devices(), CPU_PEAKS, make_cell=make_cell)


def test_program_is_correct_and_control_is_not():
    seed = 2**32 + 12
    result, lines = _execute(seed)
    assert result["correct"], lines
    base = spec.load_plugin("drivers", "library").Cell
    ctl, ctl_lines = _execute(seed, control.control_cell(base))
    assert not ctl["correct"], ctl_lines
    program = result["checks"]["x_rel_err_max"]["value"]
    high = ctl["checks"]["x_rel_err_max"]
    assert ctl["checks"]["not_ok"]["value"] == 0
    assert program < high["limit"] < high["value"], (program, high)


def test_high_dot_keeps_exactly_three_bf16_passes():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 3))
    a_hi, a_lo = control._split(a)
    b_hi, b_lo = control._split(b)
    assert a_hi.dtype == a_lo.dtype == jnp.bfloat16
    f64 = lambda v: np.asarray(v.astype(jnp.float32), np.float64)  # noqa: E731
    a64 = np.asarray(a, np.float64)
    # hi is a truncated to bfloat16; lo carries the next eight bits
    assert np.all(np.abs(f64(a_hi)) <= np.abs(a64))
    assert np.allclose(f64(a_hi) + f64(a_lo), a64, rtol=2**-15, atol=0)
    assert not np.allclose(f64(a_hi), a64, rtol=2**-12, atol=0)
    want = (f64(a_hi).T @ f64(b_hi) + f64(a_hi).T @ f64(b_lo)
            + f64(a_lo).T @ f64(b_hi))
    got = np.asarray(control.high_dot(a, b), np.float64)
    assert np.allclose(got, want, rtol=1e-6, atol=1e-6)
    exact = a64.T @ np.asarray(b, np.float64)
    assert not np.allclose(got, exact, rtol=1e-6, atol=1e-6)
