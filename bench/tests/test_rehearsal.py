"""A CPU rehearsal of every cell: the run after the look for a chip
(``run.execute``) drives the cell's traffic through the timed path at a
tiny size and decides ``correct``. Then the timed path is broken
underneath, once for each fault the cell can have, and ``correct`` has to
come out false."""

import jax
import jax.numpy as jnp
import pytest

import cell
import run
import spec
import tiny

SEED = 2**31 + 17
CPU_PEAKS = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}


def _metrics(name):
    return spec.metrics_for(spec.load_benchmark(), name, trace=False)


def execute(name, seconds=1.0):
    wl, cfg, mix = tiny.cell(name)
    return run.execute(wl, cfg, mix, _metrics(name), cell.limits(name),
                       SEED, seconds, False, jax.devices(), CPU_PEAKS)


def _assert_sound(result, lines, name):
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in _metrics(name)}
    assert set(result["metrics"]) == want
    assert list(result)[-1] == "checks"
    assert lines[-1].startswith("check not_ok")
    assert "compiles in window: 0" in lines


@pytest.mark.parametrize("name", sorted(tiny.CUTS))
def test_cell_rehearses_correct(name):
    result, lines = execute(name)
    _assert_sound(result, lines, name)


# -- faults planted under the timed path ------------------------------------

def _zero(q, x, stats):                   # a solve that returns x0 unchanged
    return jnp.zeros_like(x), stats


def _alter(q, x, stats):                  # an answer altered where produced
    return x.at[..., 0].add(1e-2 * jnp.linalg.norm(x, axis=-1)), stats


LIB_FAULTS = {"state_unchanged": _zero, "answer_altered": _alter}


@pytest.mark.parametrize("fault", sorted(LIB_FAULTS))
def test_library_fault_is_caught(monkeypatch, fault):
    import repro.core as core

    real = core.padded_adaptive_solve

    def broken(q, *a, **k):
        return LIB_FAULTS[fault](q, *real(q, *a, **k))

    monkeypatch.setattr(core, "padded_adaptive_solve", broken)
    result, lines = execute("lib-expdecay-16k", seconds=0.5)
    assert not result["correct"], lines
    assert result["checks"]["x_rel_err_max"]["value"] > \
        result["checks"]["x_rel_err_max"]["limit"]
