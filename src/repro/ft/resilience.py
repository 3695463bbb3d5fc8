"""Node-failure resilience: elastic re-meshing, straggler detection,
preemption handling. The policies are framework-level (orchestrator hooks on
a real pod); the mechanisms are implemented and unit-tested here.
"""

from __future__ import annotations

import dataclasses
import signal
import statistics
import time
from typing import Callable, Optional

from jax.sharding import AbstractMesh, AxisType


def _abstract_mesh(shape: tuple[int, ...], names: tuple[str, ...]):
    return AbstractMesh(shape, names,
                        axis_types=tuple(AxisType.Auto for _ in names))


# ---------------------------------------------------------------------------
# Elastic scaling: rebuild mesh from live device count + reshard via ckpt
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ElasticPlan:
    n_devices: int
    mesh: "jax.sharding.AbstractMesh"
    per_device_batch: int
    num_microbatches: int


def plan_mesh_shape(n_devices: int) -> tuple[int, int]:
    """(data, model) for an arbitrary live-device count — prefers model=16,
    else the largest power-of-two divisor ≤ 16."""
    model = 1
    for cand in (16, 8, 4, 2):
        if n_devices % cand == 0:
            model = cand
            break
    return n_devices // model, model


def plan_elastic(global_batch: int, n_live_devices: int,
                 target_microbatch: int = 32) -> ElasticPlan:
    """Largest usable mesh for the live-device count + a batch plan that
    preserves the *global* batch (grad-equivalent training after restart).

    Planning uses an AbstractMesh (no device objects needed — callable from
    the controller before the new slice is up); ``launch.mesh
    .make_elastic_mesh`` realizes it against live devices at restart.
    Devices that don't fit the mesh shape are left idle (hot spares)."""
    data, model = plan_mesh_shape(n_live_devices)
    # the data axis must divide the global batch: shrink it to the largest
    # divisor ≤ data (excess devices idle as hot spares)
    while global_batch % data:
        data -= 1
    mesh = _abstract_mesh((data, model), ("data", "model"))
    nmb = max(1, global_batch // target_microbatch)
    while global_batch % nmb:
        nmb -= 1
    return ElasticPlan(
        n_devices=mesh.size,
        mesh=mesh,
        per_device_batch=global_batch // data,
        num_microbatches=nmb,
    )


# ---------------------------------------------------------------------------
# Straggler mitigation: per-step timing watchdog
# ---------------------------------------------------------------------------

class StragglerWatchdog:
    """Tracks per-step (or per-host heartbeat) durations; flags outliers.

    On a real pod the flagged host is reported to the orchestrator which
    drains and replaces it; here the policy hook is injectable and the
    detection logic is unit-tested. Detection: a step is a straggler event
    if it exceeds ``factor`` × running median over the window; a host is
    flagged after ``patience`` consecutive events.
    """

    def __init__(self, window: int = 50, factor: float = 2.0,
                 patience: int = 3,
                 on_flag: Optional[Callable[[str, float], None]] = None):
        self.window = window
        self.factor = factor
        self.patience = patience
        self.on_flag = on_flag or (lambda host, t: None)
        self._times: list[float] = []
        self._consecutive: dict[str, int] = {}
        self.flagged: list[str] = []

    def median(self) -> Optional[float]:
        return statistics.median(self._times) if self._times else None

    def record(self, duration_s: float, host: str = "host0") -> bool:
        """Returns True if this step was a straggler event."""
        med = self.median()
        self._times.append(duration_s)
        if len(self._times) > self.window:
            self._times.pop(0)
        if med is None or len(self._times) < 5:
            return False
        if duration_s > self.factor * med:
            c = self._consecutive.get(host, 0) + 1
            self._consecutive[host] = c
            if c >= self.patience and host not in self.flagged:
                self.flagged.append(host)
                self.on_flag(host, duration_s)
            return True
        self._consecutive[host] = 0
        return False


# ---------------------------------------------------------------------------
# Preemption: SIGTERM → checkpoint-and-exit
# ---------------------------------------------------------------------------

class PreemptionHandler:
    """Installs a SIGTERM/SIGINT handler that raises a request flag; the
    train loop checks ``should_stop`` each step and checkpoints before
    exiting (TPU preemption notices give ~30 s)."""

    def __init__(self, signals=(signal.SIGTERM,)):
        self._stop = False
        self._signals = signals
        self._old = {}

    def __enter__(self):
        for s in self._signals:
            self._old[s] = signal.signal(s, self._handler)
        return self

    def __exit__(self, *exc):
        for s, h in self._old.items():
            signal.signal(s, h)
        return False

    def _handler(self, signum, frame):
        self._stop = True

    @property
    def should_stop(self) -> bool:
        return self._stop


# ---------------------------------------------------------------------------
# Restartable step-runner glue (used by launch/train.py)
# ---------------------------------------------------------------------------

def run_with_restarts(step_fn, n_steps: int, ckpt, state, *, save_every: int,
                      start_step: int = 0, watchdog: StragglerWatchdog | None = None,
                      preempt: PreemptionHandler | None = None):
    """Drive step_fn(state)->state with periodic async checkpoints,
    straggler tracking, and preemption-safe exit. Returns (state, last_step)."""
    step = start_step
    while step < n_steps:
        t0 = time.perf_counter()
        state = step_fn(state)
        dt = time.perf_counter() - t0
        step += 1
        if watchdog is not None:
            watchdog.record(dt)
        if step % save_every == 0:
            ckpt.save(step, state, blocking=False)
        if preempt is not None and preempt.should_stop:
            ckpt.wait()
            ckpt.save(step, state, blocking=True)
            break
    ckpt.wait()
    return state, step
