"""Device time by engine phase and idle time by program span
(``bench/phases.py``), on synthetic events and on a synthetic
``.xplane.pb`` that carries the scopes as a TPU trace does."""

import pytest

import phases
import reduction
from reduction import Event

MS = 1e6  # ns


def ev(name, start_ms, dur_ms, module=""):
    return Event(name, start_ms * MS, dur_ms * MS, module)


def hlo(instr, op):
    return f"%{instr} = f32[16,256]{{1,0:T(8,128)}} {op}(f32[16,256] %p.1)"


def test_scope_of_takes_the_first_engine_component():
    assert phases.scope_of(
        "jit(padded_adaptive_solve_batched)/engine.loop/while/body/"
        "dot_general:") == "engine.loop"
    assert phases.scope_of("jit(f)/engine.sketch/jit(gaussian_sa)/"
                           "pallas_call:") == "engine.sketch"
    assert phases.scope_of("jit(f)/mul:") == ""
    assert phases.scope_of("jit(f)/my_engine.loop/x") == ""


def test_scopes_split_op_time_and_skip_containers():
    # window 0..100 ms; a while loop (a container) encloses two loop ops,
    # an unscoped copy, and a factor op that runs past the window's end
    host = [ev("bench.window", 0, 100), ev("bench.solve", 0, 100)]
    dev = {0: [ev(hlo("while.1", "while"), 10, 30, "jit_solve"),
               ev(hlo("fusion.2", "fusion"), 10, 10, "jit_solve"),
               ev(hlo("fusion.3", "fusion"), 25, 15, "jit_solve"),
               ev(hlo("copy.4", "copy"), 50, 5, "jit_solve"),
               ev(hlo("custom-call.5", "custom-call"), 90, 20, "jit_solve")]}
    scopes = {0: ["engine.loop", "engine.loop", "engine.loop", "",
                  "engine.factor"]}
    p = phases.reduce_phases(dev, scopes, host)
    assert p.scope_s == {"engine.loop": pytest.approx(0.025),
                         "engine.factor": pytest.approx(0.010),
                         "unscoped": pytest.approx(0.005)}
    # the scopes sum to the non-container op time inside the window
    assert sum(p.scope_s.values()) == pytest.approx(0.040)
    # the unscoped copy ran after a loop op
    assert p.unscoped_after == {"engine.loop": pytest.approx(0.005)}
    assert p.calls == 1
    assert ["engine.loop", "jit_solve/fusion", pytest.approx(0.025)] in p.ops
    assert not any("while" in name for _, name, _ in p.ops)
    # the same events through the benchmark's reduction: same busy time
    s = reduction.reduce_events(dev, host, {})
    busy = 0.030 + 0.005 + 0.010
    assert s.busy_s == pytest.approx(busy)
    assert sum(v for _, v in p.idle_gaps) == pytest.approx(0.1 - busy)


def test_scope_time_is_averaged_over_devices():
    host = [ev("bench.window", 0, 10)]
    dev = {0: [ev(hlo("fusion.1", "fusion"), 0, 4)],
           1: [ev(hlo("fusion.1", "fusion"), 0, 2)]}
    p = phases.reduce_phases(dev, {0: ["engine.loop"], 1: ["engine.loop"]},
                             host)
    assert p.scope_s == {"engine.loop": pytest.approx(0.003)}
    assert p.devices == 2


def test_idle_gaps_take_the_innermost_program_span():
    # bench.solve 0..100 holds repro.solve.prepare 0..10, .dispatch
    # 10..20, .unpack 80..100; the device runs 15..70
    host = [ev("bench.window", 0, 120), ev("bench.solve", 0, 100),
            ev("repro.solve.prepare", 0, 10),
            ev("repro.solve.dispatch", 10, 10),
            ev("repro.solve.unpack", 80, 20),
            ev("other.span", 70, 10)]
    dev = {0: [ev(hlo("fusion.1", "fusion"), 15, 55, "jit_solve")]}
    p = phases.reduce_phases(dev, {0: ["engine.loop"]}, host)
    # idle 0..15 (mid 7.5: prepare), 70..120 (mid 95: unpack)
    assert dict(p.idle_gaps) == {"repro.solve.prepare": pytest.approx(0.015),
                                 "repro.solve.unpack": pytest.approx(0.050)}
    # the benchmark's reduction sees only the bench.* span there
    s = reduction.reduce_events(dev, host, {})
    assert dict(s.idle_gaps) == {"bench.solve": pytest.approx(0.065)}
    # outside every span the label is the reduction's own
    p2 = phases.reduce_phases(dev, {0: ["engine.loop"]},
                              [ev("bench.window", 0, 120)])
    assert dict(p2.idle_gaps) == {"no bench span": pytest.approx(0.065)}
    # a trace with no window span (an operator's own): the ops' extent
    p3 = phases.reduce_phases(dev, {0: ["engine.loop"]}, host[1:])
    assert p3.window_s == pytest.approx(0.055) and p3.idle_gaps == []


def test_a_trace_without_program_scopes_or_spans_reads_as_before():
    # the reduction test's own trace: the same idle gaps, and no scopes
    host = [ev("bench.window", 0, 100), ev("bench.flush", 0, 60),
            ev("bench.submit", 60, 40), ev("bench.solve", 20, 20)]
    fwht = hlo("vmap_jit_fwht__.1", "custom-call") + \
        ', custom_call_target="tpu_custom_call"'
    dev = {0: [ev(fwht, 10, 10, "jit_solve"),
               ev(hlo("fusion.2", "fusion"), 15, 15, "jit_solve"),
               ev(hlo("all-reduce.3", "all-reduce"), 60, 10),
               ev(hlo("while.5", "while"), 9, 22),
               ev(hlo("fusion.9", "fusion"), 95, 10)],
           1: [ev(hlo("custom-call.7", "custom-call"), -5, 55)]}
    p = phases.reduce_phases(dev, {}, host)
    s = reduction.reduce_events(dev, host, {})
    assert p.scope_s == {} and p.unscoped_after == {}
    assert p.idle_gaps == s.idle_gaps
    assert p.window_s == s.window_s and p.devices == s.devices
    assert [[name, v] for _, name, v in p.ops] == s.device_ops


def test_needs_a_window_or_device_events():
    with pytest.raises(ValueError):
        phases.reduce_phases({0: []}, {}, [ev("bench.solve", 0, 1)])


XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 60000000 }
  }
  lines {
    id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 45000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%gaussian_sa.1 = f32[16,256]{1,0} custom-call(), "
      "custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 1
      str_value: "jit(padded_adaptive_solve_batched)/engine.sketch/jit(gaussian_sa)/pallas_call:" }
    stats { metadata_id: 2 str_value: "loop fusion" } } }
  event_metadata { key: 2 value { id: 2
    name: "%multiply_reduce_fusion = f32[256]{0} fusion(f32[256]{0} %p)"
    stats { metadata_id: 1 ref_value: 3 } } }
  event_metadata { key: 3 value { id: 3
    name: "%copy.1 = f32[256]{0} copy(f32[256]{0} %p)" } }
  event_metadata { key: 9 value { id: 9
    name: "jit_padded_adaptive_solve_batched(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
  stat_metadata { key: 3 value { id: 3
    name: "jit(padded_adaptive_solve_batched)/engine.loop/while/body/mul:" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 90000000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 70000000 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.solve" } }
  event_metadata { key: 3 value { id: 3 name: "repro.solve.dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "repro.solve.unpack" } }
}
"""


def test_scopes_are_read_from_the_op_metadata_of_an_xplane(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert phases.op_scopes(path) == {0: [
        ("%gaussian_sa.1 = f32[16,256]{1,0} custom-call(), "
         "custom_call_target=\"tpu_custom_call\"", "engine.sketch"),
        ("%multiply_reduce_fusion = f32[256]{0} fusion(f32[256]{0} %p)",
         "engine.loop"),
        ("%copy.1 = f32[256]{0} copy(f32[256]{0} %p)", ""),
        ("%gaussian_sa.1 = f32[16,256]{1,0} custom-call(), "
         "custom_call_target=\"tpu_custom_call\"", "engine.sketch")]}
    p = phases.summarize(tmp_path)
    # ops (ns from 1000): kernel 1000..11000 and 46000..56000, loop
    # fusion 11000..41000, copy 41000..46000; window 0..100000
    assert p.scope_s == {"engine.loop": pytest.approx(30e-6),
                         "engine.sketch": pytest.approx(20e-6),
                         "unscoped": pytest.approx(5e-6)}
    assert p.unscoped_after == {"engine.loop": pytest.approx(5e-6)}
    assert p.ops[0][:2] == ["engine.loop", "jit_padded_adaptive_solve_batched"
                                           "/multiply_reduce_fusion"]
    assert p.calls == 1
    # idle 0..1000 (mid 500: dispatch opened at 500), 56000..100000
    # (mid 78000: unpack)
    assert dict(p.idle_gaps) == {"repro.solve.dispatch": pytest.approx(1e-6),
                                 "repro.solve.unpack": pytest.approx(44e-6)}
    lines = phases.report(p)
    assert "1 call(s); ms per call" in lines[0]
    assert "engine.loop 0.0300" in lines[1] and "90.909% scoped" in lines[1]


def test_a_decoded_op_list_that_does_not_match_is_refused(tmp_path,
                                                          monkeypatch):
    from jax.profiler import ProfileData

    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    decoded = phases.op_scopes(path)
    monkeypatch.setattr(phases, "op_scopes", lambda _: {0: decoded[0][:-1]})
    with pytest.raises(ValueError, match="TPU:0: 3 decoded"):
        phases.read(path)


def test_calls_come_from_solve_spans_else_dispatch_spans_else_totals():
    dev = {0: [ev(hlo("fusion.1", "fusion"), 10, 10, "jit_solve"),
               ev(hlo("fusion.1", "fusion"), 40, 20, "jit_solve")]}
    scopes = {0: ["engine.loop", "engine.loop"]}
    dispatch = [ev("repro.solve.dispatch", 5, 1),
                ev("repro.solve.dispatch", 35, 1)]
    solves = [ev("bench.solve", 4, 20), ev("bench.solve", 34, 30),
              ev("bench.solve", 200, 1)]      # outside the window
    window = [ev("bench.window", 0, 100)]
    assert phases.reduce_phases(dev, scopes, window + solves + dispatch
                                ).calls == 2
    p = phases.reduce_phases(dev, scopes, window + dispatch[1:])
    assert p.calls == 1
    assert "engine.loop 30.0000" in phases.report(p)[1]
    # an operator's trace with no spans at all: totals over the ops' extent
    p = phases.reduce_phases(dev, scopes, [])
    assert p.calls == 0 and p.window_s == pytest.approx(0.050)
    lines = phases.report(p)
    assert "no call span; ms in all" in lines[0]
    assert "engine.loop 30.0000" in lines[1]
