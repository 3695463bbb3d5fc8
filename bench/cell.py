"""What a driver hands back from a run, and the comparison that decides
``correct``.

Every driver (``bench/drivers/<entry>.py``) records one ``Answer`` per
request that was due in the window, with the x the timed path returned,
and the index of the pool problem it answered. After the window the
answers are compared with the float64 reference of their problem
(``bench/reference.py``) against the limits in ``bench/checks/<cell>.json``.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any

import numpy as np

from reference import rel_err, ridge_ref

CHECKS = Path(__file__).resolve().parent / "checks"


@dataclasses.dataclass
class Answer:
    pool_index: int
    latency_s: float       # due time (or call) to the answer
    status: str            # "OK" when the answer came back certified
    x: Any                 # the answer in the request's coordinates
    iters: int | None = None
    info: dict = dataclasses.field(default_factory=dict)  # its certificate


@dataclasses.dataclass
class Window:
    seconds: float         # first due time to the last answer
    attempted: int
    answers: list
    engine_calls: int
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> int:
        return sum(a.status == "OK" for a in self.answers)

    @property
    def failed(self) -> int:
        return self.attempted - self.ok


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def passed(self) -> bool:
        return self.value <= self.limit


def limits(cell: str) -> dict:
    path = CHECKS / f"{cell}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no check limits for {cell!r} at {path}")
    return json.loads(path.read_text())


def compare(window: Window, problems: list, data: list,
            cell_limits: dict) -> tuple[list[Check], list[str]]:
    """Every answer of the window against the float64 reference of its
    problem. Returns the checks and lines that describe the worst answer."""
    limit = float(cell_limits["x_rel_err_max"]["limit"])
    refs: dict[int, np.ndarray] = {}
    worst, worst_answer, above = -1.0, None, 0
    for a in window.answers:
        p = a.pool_index
        if p not in refs:
            A, y = data[p]
            refs[p] = ridge_ref(np.asarray(A), np.asarray(y), problems[p].nu)
        err = rel_err(a.x, refs[p])
        if not np.isfinite(err):
            err = float("inf")
        above += err > limit
        if err > worst:
            worst, worst_answer = err, a
    if worst_answer is None:
        worst = float("inf")
    checks = [Check("x_rel_err_max", worst, limit),
              Check("not_ok", float(window.failed), 0.0)]
    notes = [f"answers above the limit: {above} of {len(window.answers)}"]
    if worst_answer is not None:
        p = problems[worst_answer.pool_index]
        notes.append(
            f"worst answer: problem {worst_answer.pool_index} (n {p.n}, d "
            f"{p.d}, nu {p.nu:.6g}, rate {p.rate:.6g}) status "
            f"{worst_answer.status} {worst_answer.info}")
    return checks, notes
