"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``jax.profiler.ProfileData`` reads it: planes (one per TPU, one for the
host), their lines, and events with a start and a duration in ns. On a TPU
plane the line ``XLA Ops`` holds one event per HLO operation run, named
by the instruction's HLO text (``%gaussian_sa.1 = f32[...] custom-call(...),
custom_call_target="tpu_custom_call"``), and ``XLA Modules`` one per
executable run. A Pallas kernel is a ``tpu_custom_call``; its instruction
is named after the jitted wrapper that called it (``gaussian_sa``,
``vmap_jit_fwht__``). While, conditional and call ops enclose the ops of
their bodies: they count towards busy time and in no op's time. The host
plane holds the benchmark's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``) on the same clock.

What comes out (``Summary``), for the devices the cell uses:

* ``window_s``: the traced window, the ``bench.window`` span;
* ``busy_s``: the union of op intervals inside the window, per device,
  averaged over the devices;
* ``family_s``: device time of each kernel family's Pallas events (the
  instruction names hold a substring that ``bench/work/<family>.py``
  lists), summed per device and averaged over the devices;
* ``allreduce_s``: the same for all-reduce operations;
* ``device_ops``: the ten operations that took most device time, as
  ``<module>/<op>`` with the op's number dropped;
* ``idle_gaps``: idle device time inside the window, by the innermost
  ``bench.*`` host span open at the middle of each gap.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
PALLAS = 'custom_call_target="tpu_custom_call"'
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CONTAINER = re.compile(r"[)\]}] (while|conditional|call)\(")
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Event:
    name: str              # on a TPU, the HLO instruction's text
    start_ns: float
    dur_ns: float
    module: str = ""       # the XLA module that ran it

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def instruction(self) -> str:
        m = _INSTR.match(self.name)
        return m.group(1) if m else self.name

    @property
    def container(self) -> bool:
        """A while/conditional/call op: its span encloses other ops."""
        return bool(_CONTAINER.search(self.name.split(" = ", 1)[-1]))

    @property
    def short(self) -> str:
        """A stable label: Pallas kernels and library calls by what they
        run, other ops by their instruction name without its number."""
        base = _SUFFIX.sub("", self.instruction)
        if PALLAS in self.name:
            return f"pallas:{base}"
        t = _TARGET.search(self.name)
        if t:
            return f"custom-call:{t.group(1)}"
        return base

    def of_family(self, keys: tuple[str, ...]) -> bool:
        return PALLAS in self.name and any(k in self.instruction
                                           for k in keys)

    @property
    def allreduce(self) -> bool:
        return "all-reduce" in self.instruction


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    family_s: dict
    allreduce_s: float
    device_ops: list
    idle_gaps: list


def union_length(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total covered length of [start, end) intervals, and the sorted
    merged intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _clip(ev: Event, lo: float, hi: float) -> tuple[float, float] | None:
    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
    return (s, e) if e > s else None


def reduce_events(device_events: dict[int, list[Event]],
                  host_spans: list[Event],
                  families: dict[str, tuple[str, ...]]) -> Summary:
    """The reduction itself, on plain events (tests feed synthetic ones)."""
    windows = [s for s in host_spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w = max(windows, key=lambda s: s.dur_ns)
    lo, hi = w.start_ns, w.end_ns
    spans = [s for s in host_spans if s.name != WINDOW_SPAN
             and s.name.startswith("bench.")]

    busy, fam, ar, gaps = [], {f: 0.0 for f in families}, 0.0, {}
    ops: dict[str, float] = {}
    for _, events in sorted(device_events.items()):
        clipped = []
        for ev in events:
            iv = _clip(ev, lo, hi)
            if iv is None:
                continue
            clipped.append(iv)
            if ev.container:
                continue
            dur = iv[1] - iv[0]
            label = f"{ev.module}/{ev.short}" if ev.module else ev.short
            ops[label] = ops.get(label, 0.0) + dur
            for f, keys in families.items():
                if ev.of_family(keys):
                    fam[f] += dur
            if ev.allreduce:
                ar += dur
        covered, merged = union_length(clipped)
        busy.append(covered)
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                mid = 0.5 * (prev + s)
                open_ = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
                label = (min(open_, key=lambda sp: sp.dur_ns).name
                         if open_ else "no bench span")
                gaps[label] = gaps.get(label, 0.0) + (s - prev)
            prev = max(prev, e)
    k = max(1, len(device_events))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / k * 1e-9,
        devices=len(device_events),
        family_s={f: v / k * 1e-9 for f, v in fam.items()},
        allreduce_s=ar / k * 1e-9,
        device_ops=[[name, v / k * 1e-9] for name, v in top],
        idle_gaps=[[name, v / k * 1e-9] for name, v in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    )


def _with_modules(ops: list[Event], modules: list[Event]) -> list[Event]:
    """Name each op's XLA module: the module event that encloses its start."""
    modules = sorted(modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in modules]
    for ev in ops:
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if i >= 0 and ev.start_ns < modules[i].end_ns:
            ev.module = modules[i].name.split("(", 1)[0]
    return ops


def read_xplane(path: Path, device_ids: set[int] | None = None):
    """(device_events, host_spans) from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if device_ids is not None and dev not in device_ids:
                continue
            lines = {line.name: [Event(e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            if OPS_LINE in lines:
                devices[dev] = _with_modules(lines[OPS_LINE],
                                             lines.get(MODULES_LINE, []))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(Event(e.name, float(e.start_ns),
                                   float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith("bench."))
    return devices, spans


def summarize(trace_dir: Path, families: dict[str, tuple[str, ...]],
              device_ids: set[int] | None = None) -> Summary:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, spans = read_xplane(files[-1], device_ids)
    if not devices:
        raise ValueError(f"no TPU plane with an '{OPS_LINE}' line in "
                         f"{files[-1]}")
    return reduce_events(devices, spans, families)
