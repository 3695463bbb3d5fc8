#!/usr/bin/env python3
"""Device time by engine phase, and idle device time by program span,
from one profiler trace.

    python3 bench/phases.py <trace dir or .xplane.pb>

The padded engine traces each piece of a solve under one named scope
(``engine.sketch``, ``engine.factor``, ``engine.gram``, ``engine.init``,
``engine.loop``, ``engine.finalize``; DESIGN.md §14), and XLA keeps the
scope in every op's ``op_name``. On a TPU trace that name is the ``tf_op``
stat of the op's event metadata on the ``XLA Ops`` line, e.g.
``jit(padded_adaptive_solve_batched)/engine.loop/while/body/dot_general:``.
``jax.profiler.ProfileData`` shows an event's own stats but not those of
its metadata, so the metadata is decoded here from the ``.xplane.pb``
itself: a protobuf ``XSpace``, of which only the few fields named below
are read. Ops the compiler makes itself carry no ``tf_op``.

The library entry ``padded_adaptive_solve`` writes ``repro.solve.prepare``,
``repro.solve.dispatch`` and ``repro.solve.unpack`` host spans; they share
the profiler's clock with the device planes, as the benchmark's own
``bench.*`` spans do.

What comes out (``Phases``), with the window, clipping, exclusion of
while/conditional/call containers and averaging over devices of
``reduction.reduce_events``:

* ``scope_s``: device time of each scope (the first ``engine.*``
  component of the op's name), and ``unscoped`` for the rest; empty when
  no op has a scope, as in a trace of a program without them;
* ``unscoped_after``: the ``unscoped`` time again, split by the scope of
  the last scoped op that ran before it in the same module; an inference
  from the schedule (XLA runs one op at a time and keeps the phases
  mostly in order), not a reading of the op's name;
* ``ops``: the ten operations that took most device time, each with its
  scope;
* ``idle_gaps``: idle device time inside the window, by the innermost
  ``bench.*`` or ``repro.*`` host span open at the middle of each gap
  (the ten largest);
* ``calls``: what the report divides by: the ``bench.solve`` spans that
  start in the window, else the ``repro.solve.dispatch`` spans, else
  none, and the report gives totals.
"""

from __future__ import annotations

import argparse
import dataclasses
import re
import sys
from pathlib import Path

import reduction
from reduction import DEVICE_PLANE, HOST_PLANE, OPS_LINE, WINDOW_SPAN

SCOPE_STAT = "tf_op"
UNSCOPED = "unscoped"
NO_SCOPE_BEFORE = "none"
SPAN_PREFIXES = ("bench.", "repro.")
_SCOPE = re.compile(r"(?:^|/)(engine\.\w+)")

# XSpace field numbers (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_META_ID = 1
_MAP_VALUE = 2
_META_ID, _META_NAME, _META_STATS = 1, 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _fields(buf: bytes):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, bytes for a length-delimited field; fixed-width fields
    are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def scope_of(op_name: str) -> str:
    """The first ``engine.*`` component of an op's name stack, or ''."""
    m = _SCOPE.search(op_name)
    return m.group(1) if m else ""


def _map_value(entry: bytes) -> bytes:
    return next((v for f, v in _fields(entry) if f == _MAP_VALUE), b"")


def _plane_scopes(plane: bytes) -> tuple[str, list[tuple[str, str]]]:
    """A plane's name, and (event name, scope) of each event of its
    ``XLA Ops`` line in the file's order."""
    name, lines, metas, stat_metas = "", [], [], []
    for f, v in _fields(plane):
        if f == _PLANE_NAME:
            name = v.decode()
        elif f == _PLANE_LINES:
            lines.append(v)
        elif f == _PLANE_EVENT_META:
            metas.append(v)
        elif f == _PLANE_STAT_META:
            stat_metas.append(v)
    if not DEVICE_PLANE.match(name):
        return name, []
    stat_names = {}
    for entry in stat_metas:
        fs = dict(_fields(_map_value(entry)))
        stat_names[fs.get(_META_ID, 0)] = fs.get(_META_NAME, b"").decode()
    scope_stat = {i for i, s in stat_names.items() if s == SCOPE_STAT}
    meta = {}
    for entry in metas:
        mid, mname, scope = 0, "", ""
        for f, v in _fields(_map_value(entry)):
            if f == _META_ID:
                mid = v
            elif f == _META_NAME:
                mname = v.decode(errors="replace")
            elif f == _META_STATS:
                stat = dict(_fields(v))
                if stat.get(_STAT_META_ID) in scope_stat:
                    text = (stat[_STAT_STR].decode(errors="replace")
                            if _STAT_STR in stat
                            else stat_names.get(stat.get(_STAT_REF), ""))
                    scope = scope_of(text)
        meta[mid] = (mname, scope)
    events = []
    for line in lines:
        fs = list(_fields(line))
        if not any(f == _LINE_NAME and v == OPS_LINE.encode()
                   for f, v in fs):
            continue
        for f, v in fs:
            if f == _LINE_EVENTS:
                mid = next((x for g, x in _fields(v) if g == _EVENT_META_ID),
                           0)
                events.append(meta.get(mid, ("", "")))
    return name, events


def op_scopes(path: Path) -> dict[int, list[tuple[str, str]]]:
    """For each TPU plane of an ``.xplane.pb``, (name, scope) of every
    event of its ``XLA Ops`` line, in the order ``ProfileData`` gives."""
    out = {}
    for f, plane in _fields(Path(path).read_bytes()):
        if f != _SPACE_PLANES:
            continue
        name, events = _plane_scopes(plane)
        m = DEVICE_PLANE.match(name)
        if m:
            out[int(m.group(1))] = events
    return out


def read(path: Path):
    """(device_events, scopes, host_spans) of one ``.xplane.pb``: the op
    events exactly as ``reduction.read_xplane`` gives them, the scope of
    each ('' where it has none), and the host spans named ``bench.*`` or
    ``repro.*``. Raises where the decoded metadata and ``ProfileData``'s
    events of a device do not line up one to one."""
    from jax.profiler import ProfileData

    devices, _ = reduction.read_xplane(path)
    decoded = op_scopes(path)
    scopes = {}
    for dev, events in devices.items():
        pairs = decoded.get(dev, [])
        if len(pairs) != len(events) or any(
                n != e.name for (n, _), e in zip(pairs, events)):
            raise ValueError(
                f"TPU:{dev}: {len(pairs)} decoded '{OPS_LINE}' events do not "
                f"match the {len(events)} that ProfileData reads in {path}")
        scopes[dev] = [s for _, s in pairs]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(reduction.Event(e.name, float(e.start_ns),
                                             float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return devices, scopes, spans


@dataclasses.dataclass
class Phases:
    window_s: float
    devices: int
    calls: int                 # bench.solve spans in the window, else
                               # repro.solve.dispatch spans, else 0
    scope_s: dict
    unscoped_after: dict
    ops: list                  # [scope, <module>/<op>, seconds]
    idle_gaps: list            # [span, seconds]


def reduce_phases(device_events: dict[int, list],
                  scopes: dict[int, list[str]],
                  host_spans: list) -> Phases:
    """The scope split, on plain events (tests feed synthetic ones). The
    window is the longest ``bench.window`` span, or where there is none
    (an operator's own trace), the first op's start to the last op's end.
    Window, busy intervals and idle gaps are ``reduction.reduce_events``'s
    own; it labels gaps by ``bench.*`` spans only, so the ``repro.*``
    spans are handed to it under that prefix and given back their names."""
    spans = [s for s in host_spans if s.name.startswith(SPAN_PREFIXES)]
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        evs = [e for events in device_events.values() for e in events]
        if not evs:
            raise ValueError("no device events and no window span")
        lo = min(e.start_ns for e in evs)
        windows = [reduction.Event(WINDOW_SPAN, lo,
                                   max(e.end_ns for e in evs) - lo)]
        spans += windows
    w = max(windows, key=lambda s: s.dur_ns)
    lo, hi = w.start_ns, w.end_ns
    summary = reduction.reduce_events(device_events, [
        dataclasses.replace(s, name="bench." + s.name)
        if s.name.startswith("repro.") else s for s in spans], {})
    idle_gaps = [[name.removeprefix("bench.")
                  if name.startswith("bench.repro.") else name, v]
                 for name, v in summary.idle_gaps]

    def count(name):
        return sum(s.name == name and lo <= s.start_ns < hi for s in spans)

    scope_s: dict[str, float] = {}
    after: dict[str, float] = {}
    ops: dict[tuple[str, str], float] = {}
    for dev, events in sorted(device_events.items()):
        last = {}
        pairs = zip(events, scopes.get(dev, [""] * len(events)))
        for ev, scope in sorted(pairs, key=lambda es: es[0].start_ns):
            if scope and not ev.container:
                last[ev.module] = scope
            iv = reduction._clip(ev, lo, hi)
            if iv is None or ev.container:
                continue
            dur = iv[1] - iv[0]
            if not scope:
                before = last.get(ev.module, NO_SCOPE_BEFORE)
                after[before] = after.get(before, 0.0) + dur
            scope = scope or UNSCOPED
            scope_s[scope] = scope_s.get(scope, 0.0) + dur
            label = (scope, f"{ev.module}/{ev.short}" if ev.module
                     else ev.short)
            ops[label] = ops.get(label, 0.0) + dur
    if set(scope_s) <= {UNSCOPED}:
        scope_s, after = {}, {}
    k = max(1, len(device_events))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return Phases(
        window_s=summary.window_s,
        devices=summary.devices,
        calls=count("bench.solve") or count("repro.solve.dispatch"),
        scope_s={s: v / k * 1e-9 for s, v in
                 sorted(scope_s.items(), key=lambda kv: -kv[1])},
        unscoped_after={s: v / k * 1e-9 for s, v in
                        sorted(after.items(), key=lambda kv: -kv[1])},
        ops=[[scope, name, v / k * 1e-9] for (scope, name), v in top],
        idle_gaps=idle_gaps,
    )


def summarize(trace: Path) -> Phases:
    trace = Path(trace)
    files = [trace] if trace.is_file() else sorted(
        trace.rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace}")
    return reduce_phases(*read(files[-1]))


def report(p: Phases) -> list[str]:
    """The tables in ms per call, or in ms in all where no call span was
    found."""
    ms = 1e3 / max(1, p.calls)
    scoped = sum(v for s, v in p.scope_s.items() if s != UNSCOPED)
    total = sum(p.scope_s.values())
    lines = [f"window {p.window_s:.6f} s, {p.devices} device(s), "
             + (f"{p.calls} call(s); ms per call" if p.calls
                else "no call span; ms in all")]
    if p.scope_s:
        lines.append("device time by phase: " + ", ".join(
            f"{s} {v * ms:.4f}" for s, v in p.scope_s.items())
            + f" ({100.0 * scoped / total:.3f}% scoped)")
        lines.append("unscoped, by the phase of the scoped op before it: "
                     + ", ".join(f"{s} {v * ms:.4f}"
                                 for s, v in p.unscoped_after.items()))
    else:
        lines.append("device time by phase: no op carries a scope")
    lines += [f"  {v * ms:10.4f}  {scope or UNSCOPED:16s} {name}"
              for scope, name, v in p.ops]
    lines.append("idle device time by span: " + ", ".join(
        f"{name} {v * ms:.4f}" for name, v in p.idle_gaps))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", type=Path,
                    help="a profiler trace directory or one .xplane.pb")
    args = ap.parse_args(argv)
    print("\n".join(report(summarize(args.trace))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
