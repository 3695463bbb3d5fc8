"""The trace reduction on a synthetic trace, and the kernel work counts
against shapes computed by hand."""

import pytest

import reduction
import spec
from reduction import Event

MS = 1e6  # ns


def ev(name, start_ms, dur_ms, module=""):
    return Event(name, start_ms * MS, dur_ms * MS, module)


def hlo(instr, op, extra=""):
    """An op event's name as a TPU trace writes it: the HLO text."""
    return f"%{instr} = f32[16,256]{{1,0:T(8,128)}} {op}(f32[16,256] %p.1){extra}"


FWHT = hlo("vmap_jit_fwht__.1", "custom-call",
           ', custom_call_target="tpu_custom_call"')
CHOL = hlo("custom-call.7", "custom-call", ', custom_call_target="Cholesky"')


def test_union_merges_overlaps():
    covered, merged = reduction.union_length([(0, 2), (1, 3), (5, 6)])
    assert covered == 4 and merged == [[0, 3], [5, 6]]


def test_reduce_busy_kernels_allreduce_and_gaps():
    # window 0..100 ms; device 0 busy 9..31 (a while loop around the
    # kernel 10..20 and a fusion 15..30), 60..70 (all-reduce) and 95..100;
    # device 1 busy 0..50
    host = [ev("bench.window", 0, 100), ev("bench.flush", 0, 60),
            ev("bench.submit", 60, 40), ev("bench.solve", 20, 20)]
    dev = {
        0: [ev(FWHT, 10, 10, "jit_solve"),
            ev(hlo("fusion.2", "fusion"), 15, 15, "jit_solve"),
            ev(hlo("all-reduce.3", "all-reduce"), 60, 10),
            ev(hlo("while.5", "while"), 9, 22),      # encloses 10..30
            ev(hlo("fusion.9", "fusion"), 95, 10)],  # clipped at the end
        1: [ev(CHOL, -5, 55)],                       # clipped at the start
    }
    s = reduction.reduce_events(dev, host, {"srht": ("fwht",),
                                            "gaussian": ("gaussian_sa",)})
    assert s.window_s == pytest.approx(0.1)
    # device 0: 9..31, 60..70, 95..100 -> 37 ms; device 1: 0..50 -> 50 ms
    assert s.busy_s == pytest.approx((0.037 + 0.050) / 2)
    assert s.family_s["srht"] == pytest.approx(0.010 / 2)
    assert s.family_s["gaussian"] == 0.0
    assert s.allreduce_s == pytest.approx(0.010 / 2)
    assert s.devices == 2
    gaps = dict(s.idle_gaps)
    # device 0 idle: 0..9 (flush), 31..60 (mid 45.5: flush), 70..95
    # (submit); device 1 idle: 50..100 (mid 75: submit)
    assert gaps["bench.flush"] == pytest.approx((0.009 + 0.029) / 2)
    assert gaps["bench.submit"] == pytest.approx((0.025 + 0.050) / 2)
    ops = dict(s.device_ops)
    assert s.device_ops[0][0] == "custom-call:Cholesky"
    assert ops["jit_solve/pallas:vmap_jit_fwht__"] == pytest.approx(0.005)
    assert ops["jit_solve/fusion"] == pytest.approx(0.0075)
    assert not any("while" in name for name in ops)     # a container
    # the innermost open span labels a gap
    s2 = reduction.reduce_events({0: [ev(hlo("x.1", "fusion"), 0, 21),
                                      ev(hlo("y.1", "fusion"), 39, 61)]},
                                 host, {})
    assert dict(s2.idle_gaps) == {"bench.solve": pytest.approx(0.018)}


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        reduction.reduce_events({0: []}, [ev("bench.flush", 0, 1)], {})


def test_modules_name_the_ops_they_enclose():
    ops = [ev(FWHT, 1, 1), ev(CHOL, 5, 1), ev(CHOL, 20, 1)]
    mods = [ev("jit_prepare(123)", 0, 3), ev("jit_loop(456)", 4, 10)]
    out = reduction._with_modules(ops, mods)
    assert [e.module for e in out] == ["jit_prepare", "jit_loop", ""]


def test_work_counts_by_hand():
    g = spec.load_plugin("work", "gaussian")
    # the cell's shape: n = 16384, d = 4096, m_max = 1024
    assert g.flops(16384, 4096, 1024) == 137438953472.0
    assert g.min_bytes(16384, 4096, 1024) == 4 * (16384 + 1024) * 4096
    assert g.min_bytes(16384, 4096, 1024, itemsize=2) == 142606336.0


def test_roofline_reader_uses_the_larger_bound():
    from types import SimpleNamespace

    roof = spec.load_plugin("metrics", "sketch_roofline_pct")
    calls = [dict(family="gaussian", n=2048, d=4096, m_max=64, batch=1,
                  calls=10)]
    window = SimpleNamespace(counters={"calls": calls}, engine_calls=10)
    # 10 passes: 2 * 64 * 2048 * 4096 FLOP against 4 * (2048 + 64) * 4096 B
    flop_s = 10 * 2 * 64 * 2048 * 4096 / 197e12
    byte_s = 10 * 4 * (2048 + 64) * 4096 / 819e9
    assert byte_s > flop_s
    trace = SimpleNamespace(family_s={"gaussian": 4 * byte_s})
    ctx = SimpleNamespace(window=window, trace=trace,
                          peaks={"flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9},
                          work=lambda f: spec.load_plugin("work", f))
    assert roof.read(ctx) == pytest.approx(25.0)
    assert "memory" in roof.describe(ctx)
    calls[0]["m_max"] = 1024      # now the operations bound it
    assert "compute" in roof.describe(ctx)
    ctx.trace = SimpleNamespace(family_s={"gaussian": 0.0})
    assert roof.read(ctx) is None
