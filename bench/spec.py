"""Find a cell, its configuration, its traffic mix and its metrics by name.

Everything that belongs to one configuration, one traffic mix, one metric
or one kernel family is a file of its own, found from ``BENCHMARK.json``:

    bench/configs/<config>.json    sizes, source, assumptions, guarantees
    bench/traffic/<mix>.json       parameters read by ``generator.py``
    bench/drivers/<entry>.py       how a configuration's entry point is driven
    bench/metrics/<metric>.py      ``read(ctx)`` -> number or None
    bench/work/<family>.py         operations and least bytes of a kernel

A later cell, configuration or metric is added by adding such files and
``BENCHMARK.json`` entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


class SpecError(Exception):
    """A name in ``BENCHMARK.json`` has no file, or a file is malformed."""


def load_benchmark(path: Path | None = None) -> dict:
    path = path or REPO / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"no benchmark file at {path}") from e


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload named {name!r}; known: "
                    f"{[w['name'] for w in bm['workloads']]}")


def config(bm: dict, name: str) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((REPO / c["file"]).read_text())
    raise SpecError(f"no configuration named {name!r}")


def traffic(name: str) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path.relative_to(REPO)}")
    return json.loads(path.read_text())


def metrics_for(bm: dict, cell: str, *, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace=False``) or its per-layer
    metrics (``trace=True``): those without a ``workloads`` key, and those
    whose key lists the cell."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def plugin_path(kind: str, name: str) -> Path:
    """``bench/<kind>/<name>.py``, which has to exist."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} file {path.relative_to(REPO)}")
    return path


def load_plugin(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = plugin_path(kind, name)
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Published peaks of the device; a device missing from the table is
    an error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
