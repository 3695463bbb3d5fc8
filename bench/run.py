#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``bench/spec.py``). A run

1. refuses, with a nonzero exit and no result, unless JAX finds a TPU and
   as many chips as the cell asks for;
2. makes the cell's data on the device from ``--seed``;
3. warms the cell's own shapes (all of this is ``setup_s``);
4. measures for ``--seconds`` (with ``--trace 1`` under the profiler);
5. reads the peak device memory, drops the program's state and compares
   every answer of the window with the float64 host reference;
6. prints the numbers it compared beside their limits as the last lines of
   standard error, and one JSON line as the last line of standard output.

The end-to-end metrics come from ``--trace 0`` runs, the per-layer ones
from ``--trace 1`` runs. JAX's persistent compilation cache lives in
``.bench_cache/jax`` inside the checkout, or where
``JAX_COMPILATION_CACHE_DIR`` says.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Context:
    """What a metric reader (``bench/metrics/<name>.py``) reads."""
    cell: str
    setup_s: float
    window: object                 # cell.Window
    trace: object | None           # reduction.Summary, traced runs only
    peaks: dict
    config: dict
    mix: dict

    def work(self, family: str):
        return spec.load_plugin("work", family)


class CompileCounter:
    """Executables built (compiled or loaded from the persistent cache),
    counted from JAX's monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

    def on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1

    def on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


class GcClock:
    """Time the host spends in Python's garbage collector (a
    ``gc.callbacks`` entry)."""

    def __init__(self):
        self.count, self.total_s, self.longest_s, self._t0 = 0, 0.0, 0.0, 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        self.count += 1
        self.total_s += dt
        self.longest_s = max(self.longest_s, dt)

    def __str__(self):
        return (f"{self.count} collections, {self.total_s * 1e3:.3f} ms in "
                f"all, longest {self.longest_s * 1e3:.3f} ms")


def configure_cache(jax) -> str:
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO / ".bench_cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def fail(msg: str, code: int = 2) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return code


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bm = spec.load_benchmark()
        wl = spec.workload(bm, args.workload)
        cfg = spec.config(bm, wl["config"])
        mix = spec.traffic(wl["traffic"])
        metrics = spec.metrics_for(bm, wl["name"], trace=bool(args.trace))
        for m in metrics:
            spec.plugin_path("metrics", m["name"])
        spec.plugin_path("drivers", cfg["entry"])
        import cell
        limits = cell.limits(wl["name"])
    except (spec.SpecError, FileNotFoundError, KeyError) as e:
        return fail(f"cannot set up cell {args.workload!r}: {e}")
    if not (REPO / "src" / "repro").is_dir():
        return fail("the program under test (src/repro) is not in this "
                    "checkout")
    sys.path.insert(0, str(REPO / "src"))

    import jax

    cache = configure_cache(jax)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"JAX found no TPU (platform {dev.platform!r}); "
                    "nothing was run")
    if len(devices) < int(wl["chips"]):
        return fail(f"cell {wl['name']} needs {wl['chips']} chips, JAX "
                    f"found {len(devices)}")
    try:
        peaks = spec.peaks(dev.device_kind)
    except spec.SpecError as e:
        return fail(str(e))
    result, lines = execute(wl, cfg, mix, metrics, limits, args.seed,
                            args.seconds, bool(args.trace), devices, peaks,
                            cache)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


def execute(wl: dict, cfg: dict, mix: dict, metrics: list, limits: dict,
            seed: int, seconds: float, trace: bool, devices, peaks: dict,
            cache: str = "", make_cell=None) -> tuple[dict, list[str]]:
    """Everything of a run after the look for a chip: set-up, the window,
    the comparison and the metrics. Returns the result line's object and
    the lines for standard error, the compared numbers last.
    ``make_cell`` replaces the driver's ``Cell`` (the control does)."""
    import jax

    import cell as cellmod
    import reduction

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter.on_duration)
    jax.monitoring.register_event_listener(counter.on_event)
    readers = {m["name"]: spec.load_plugin("metrics", m["name"])
               for m in metrics}
    make_cell = make_cell or spec.load_plugin("drivers", cfg["entry"]).Cell

    span = jax.profiler.TraceAnnotation
    cell = make_cell(cfg, mix, seed, devices, span)
    with span("bench.build"):
        cell.build()
    with span("bench.warm"):
        cell.warm()
    setup_s = time.perf_counter() - T_START
    setup_compiles, setup_hits = counter.compiles, counter.cache_hits
    counter.compiles = 0

    trace_dir = None
    if trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        jax.profiler.start_trace(str(trace_dir))
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    try:
        with span("bench.window"):
            window = cell.run(seconds)
    finally:
        gc.callbacks.remove(gc_clock)
        if trace:
            jax.profiler.stop_trace()
    window_compiles = counter.compiles

    used = devices[:int(wl["chips"])]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    cell.finish(window)

    summary = None
    if trace:
        families = {f: spec.load_plugin("work", f).KERNELS
                    for f in {c["family"] for c in
                              window.counters.get("calls", [])}}
        try:
            summary = reduction.summarize(trace_dir, families,
                                         {d.id for d in used})
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    with span("bench.check"):
        checks, check_notes = cellmod.compare(window, cell.problems,
                                              cell.data, limits)
    correct = all(c.passed for c in checks)

    ctx = Context(wl["name"], setup_s, window, summary, peaks, cfg, mix)
    values = {}
    lines = [f"cell {wl['name']} seed {seed}: {window.attempted} requests, "
             f"{window.ok} certified, window {window.seconds:.3f} s, set-up "
             f"{setup_s:.3f} s ({setup_compiles} executables built, "
             f"{setup_hits} from the cache {cache})",
             f"compiles in window: {window_compiles}",
             f"host garbage collection in window: {gc_clock}"]
    lines += window.counters.get("notes", [])
    lines += check_notes
    for m in metrics:
        reader = readers[m["name"]]
        v = reader.read(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if hasattr(reader, "describe"):
            lines.append(f"{m['name']}: {reader.describe(ctx)}")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed, "metrics": values, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    lines += [f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'pass' if c.passed else 'FAIL'}" for c in checks]
    return result, lines


if __name__ == "__main__":
    sys.exit(main())
