"""Drive the library entry point ``repro.core.padded_adaptive_solve``.

One caller, closed loop: solve k takes pool problem ``order[k]`` with the
sketch key ``fold_in(seed key, k)``, and the next call starts when
``block_until_ready`` on its x returns. A request's latency is the call to
that return. The library caches nothing across calls, so pool repeats do
not flatter it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import generator
from cell import Answer, Window


class Cell:
    def __init__(self, config: dict, mix: dict, seed: int, devices, span):
        self.config, self.mix, self.seed, self.span = config, mix, seed, span
        self.solver = config["solver"]
        self.problems = generator.pool(mix, base=config["problem"])
        self.data = None

    # -- set-up ------------------------------------------------------------
    def build(self) -> None:
        from repro.core.quadratic import Quadratic

        self.data = generator.make(self.problems, self.seed)
        hi = jax.lax.Precision.HIGHEST
        self.quads = []
        for p, (A, y) in zip(self.problems, self.data):
            b = jnp.matmul(A.T, y, precision=hi)
            self.quads.append(Quadratic(
                A=A, b=b, nu=jnp.asarray(p.nu, jnp.float32),
                lam_diag=jnp.ones((p.d,), jnp.float32)))
        jax.block_until_ready(self.quads)
        self.key = generator.jax_key(self.seed, 11)

    def _solve(self, k: int, p: int):
        from repro.core import padded_adaptive_solve

        s = self.solver
        return padded_adaptive_solve(
            self.quads[p], jax.random.fold_in(self.key, k),
            m_max=s["m_max"], method=s["method"], sketch=s["sketch"],
            max_iters=s["max_iters"], rho=s["rho"], tol=s["tol"],
            compute_dtype=s["compute_dtype"])

    def warm(self) -> None:
        # every pool problem has the one shape: one solve compiles it all
        jax.block_until_ready(self._solve(2**31 - 1, 0))

    # -- the window --------------------------------------------------------
    def run(self, seconds: float) -> Window:
        order = generator.order(self.mix, self.seed)
        pending = []
        t0 = time.perf_counter()
        t_end = t0 + seconds
        k = 0
        now = t0
        gap = (0.0, 0)                 # longest time between two calls
        while now < t_end:
            p = next(order)
            with self.span("bench.solve"):
                t_call = time.perf_counter()
                gap = max(gap, (t_call - now, k))
                x, stats = self._solve(k, p)
                x.block_until_ready()
                now = time.perf_counter()
            pending.append((p, now - t_call, x, stats))
            k += 1
        from repro.core.status import SolveStatus

        answers = []
        for p, lat, x, stats in pending:
            status = SolveStatus(int(stats["status"])).name
            info = {k: float(stats[k]) for k in
                    ("iters", "m_final", "doublings", "dtilde")}
            answers.append(Answer(p, lat, status, np.asarray(x),
                                  int(stats["iters"]), info))
        s, p = self.solver, self.problems[0]
        calls = [dict(family=s["sketch"], n=p.n, d=p.d, m_max=s["m_max"],
                      batch=1, calls=k)]
        slow = max(range(len(answers)), key=lambda i: answers[i].latency_s)
        notes = [f"slowest call: {answers[slow].latency_s * 1e3:.3f} ms "
                 f"(call {slow}); longest gap between calls: "
                 f"{gap[0] * 1e3:.3f} ms (before call {gap[1]})"]
        return Window(seconds=now - t0, attempted=k, answers=answers,
                      engine_calls=k,
                      counters={"calls": calls, "notes": notes})

    def finish(self, window: Window) -> None:
        """Drop the program's inputs; the answers are on the host."""
        self.quads = None
