#!/usr/bin/env python3
"""Chip smoke: the solver service's main path, compiled, on a TPU.

    python3 chip_smoke.py                # one chip: phases A and B
    python3 chip_smoke.py --four-chips   # only the sharded service path

Phase A drives ``SolverService`` the way a user does (submit, submit_glm,
submit_path, flush) at the service's largest single-chip shape classes:
16 ridge requests in the n=4096, d=256 class (gaussian sketch), 16 in the
n=16384, d=256 class (srht, 256 MiB of packed A), 4 logistic GLM requests
and 2 λ-path requests of 8 points, then a second service flushes 16 ridge
requests with ``sketch="sjlt"``. Phase B runs the library entry point
``padded_adaptive_solve`` (pcg, gaussian) on one n=2^17, d=1024 problem
with an exponentially decaying spectrum (512 MiB of A).

Every answer must come back ``OK`` (a retry, a direct-solve fallback or a
stall fails the smoke), every ridge/path answer must match a float64 numpy
solve on the host to a relative error of 1e-4, every GLM answer must clear
the Newton-decrement tolerance, and the compiled sketch-pass executable of
each family must hold a ``tpu_custom_call`` — the Pallas kernel compiled,
not a reference.

``--four-chips`` runs only the sharded path on a 4-device ("data",) mesh:
ridge requests in the n=65536, d=256 srht class of
``SHARDED_SHAPE_CLASSES``, compared with a single-device run of
``BlockEmulationProvider`` under the same per-shard keys (x to 1e-5, δ̃
within 2×), with exactly one all-reduce in the compiled precompute and A
resident on all four devices.

The script exits nonzero, and prints no result line, when JAX finds no
TPU or any check fails. Its last line is one JSON object with the device
as JAX reports it. Wall times it prints include compilation; they are not
metrics. Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.runtime import enable_compile_cache  # noqa: E402


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Group:
    """``count`` requests with n, d drawn uniformly from the ranges."""
    count: int
    n: tuple[int, int]
    d: tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of every phase. ``on_chip`` turns on the checks only a chip
    run can make (Pallas custom calls, per-device memory)."""
    classes: tuple | None            # service shape classes (None: defaults)
    ridge: tuple[Group, ...]         # phase A ridge flushes, default sketch
    glm: Group
    path: Group
    path_points: int
    sjlt: Group
    lib_n: int                       # phase B
    lib_d: int
    lib_m_max: int
    lib_rate: float
    sharded: Group                   # --four-chips
    sharded_classes: tuple | None
    on_chip: bool = True


CHIP_PLAN = Plan(
    classes=None,
    ridge=(Group(16, (3072, 4096), (192, 256)),
           Group(16, (12288, 16384), (192, 256))),
    glm=Group(4, (3072, 4096), (192, 256)),
    path=Group(2, (3072, 4096), (192, 256)),
    path_points=8,
    sjlt=Group(16, (3072, 4096), (192, 256)),
    lib_n=1 << 17, lib_d=1024, lib_m_max=1024, lib_rate=0.9965,
    sharded=Group(16, (65536, 65536), (256, 256)),
    sharded_classes=None,
)


def rel_err(x, ref) -> float:
    import numpy as np

    return float(np.linalg.norm(np.asarray(x, np.float64) - ref)
                 / np.linalg.norm(ref))


def ridge_ref(A, y, nu):
    """float64 host solve of (AᵀA + ν²I) x = Aᵀy — independent of the
    device code under test."""
    import numpy as np

    A = np.asarray(A, np.float64)
    y = np.asarray(y, np.float64)
    H = A.T @ A + nu * nu * np.eye(A.shape[1])
    return np.linalg.solve(H, A.T @ y)


def draw(group: Group, seed: int):
    """The group's (A, y) requests: Gaussian A/√n on the device, from the
    seed."""
    import jax
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for i in range(group.count):
        n = int(rng.integers(group.n[0], group.n[1] + 1))
        d = int(rng.integers(group.d[0], group.d[1] + 1))
        kA, ky = jax.random.split(jax.random.PRNGKey(seed * 1000 + i))
        A = jax.random.normal(kA, (n, d)) / np.sqrt(n)
        y = jax.random.normal(ky, (n,))
        out.append((A, y, float(rng.uniform(0.05, 0.5))))
    return out


def sketch_pass_hlo(B: int, n: int, d: int, m_max: int, sketch: str, *,
                    weighted: bool = False, mesh=None) -> str:
    """Compiled HLO of the one-touch sketch pass (``prepare_path_ladder``)
    at the shapes the service packs: the executable whose kernels the
    smoke inspects."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.adaptive_padded import prepare_path_ladder
    from repro.core.distributed import gspmd_mesh, quadratic_shardings
    from repro.core.quadratic import Quadratic

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype)

    q = Quadratic(A=shape(B, n, d), b=shape(B, d), nu=shape(B),
                  lam_diag=shape(B, d), batched=True,
                  row_weights=shape(B, n) if weighted else None)
    keys = shape(B, 2, dtype=jnp.uint32)
    if mesh is not None:
        q = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            q, quadratic_shardings(mesh, q))
        keys = jax.ShapeDtypeStruct(
            keys.shape, keys.dtype,
            sharding=NamedSharding(gspmd_mesh(mesh), PartitionSpec()))
    return prepare_path_ladder.lower(
        q, keys, m_max=m_max, sketch=sketch, gram_hvp=True,
        mesh=mesh).compile().as_text()


def check_kernel(plan: Plan, label: str, hlo_fn) -> None:
    if not plan.on_chip:
        return
    found = "tpu_custom_call" in hlo_fn()
    print(f"  {label}: tpu_custom_call {'present' if found else 'MISSING'}")
    check(found, f"{label}: no tpu_custom_call in the compiled sketch pass")


def phase_a(plan: Plan) -> None:
    """The service, one chip: ridge (gaussian, srht), GLM and path requests
    in one flush, then a second service's sjlt flush."""
    import jax
    import numpy as np

    from repro.core.objectives import synthetic_logistic_problem
    from repro.serve.solver_service import (GLMSolution, PathSolution,
                                            SolverService)

    t0 = time.perf_counter()
    svc = SolverService(plan.classes)
    ridge = {}
    for g, group in enumerate(plan.ridge):
        for A, y, nu in draw(group, seed=1 + g):
            ridge[svc.submit(A, y, nu)] = (A, y, nu)
    rng = np.random.default_rng(7)
    for i in range(plan.glm.count):
        n = int(rng.integers(plan.glm.n[0], plan.glm.n[1] + 1))
        d = int(rng.integers(plan.glm.d[0], plan.glm.d[1] + 1))
        A, y = synthetic_logistic_problem(jax.random.PRNGKey(500 + i), n, d)
        svc.submit_glm(A, y, nu=float(rng.uniform(0.1, 0.5)),
                       family="logistic")
    paths = {}
    for A, y, _ in draw(plan.path, seed=11):
        nus = np.geomspace(1.0, 1e-2, plan.path_points)
        paths[svc.submit_path(A, y, nus)] = (A, y, nus)
    sols = svc.flush()
    print(f"phase A flush 1: {len(sols)} requests, "
          f"{time.perf_counter() - t0:.1f} s wall (incl. compile)")

    worst = 0.0
    statuses = {}
    for rid, s in sols.items():
        if isinstance(s, GLMSolution):
            statuses[s.status] = statuses.get(s.status, 0) + 1
            check(s.status == "OK" and s.converged
                  and s.decrement <= svc.newton_tol,
                  f"GLM request {rid}: status {s.status}, decrement "
                  f"{s.decrement!r} (tol {svc.newton_tol})")
        elif isinstance(s, PathSolution):
            A, y, nus = paths[rid]
            for p in s.points:
                statuses[p.status] = statuses.get(p.status, 0) + 1
                check(p.status == "OK", f"path request {rid} at ν={p.nu}: "
                      f"status {p.status}")
                worst = max(worst, rel_err(p.x, ridge_ref(A, y, p.nu)))
        else:
            statuses[s.status] = statuses.get(s.status, 0) + 1
            check(s.status == "OK",
                  f"ridge request {rid}: status {s.status}")
            A, y, nu = ridge[rid]
            worst = max(worst, rel_err(s.x, ridge_ref(A, y, nu)))

    t1 = time.perf_counter()
    svc2 = SolverService(plan.classes, sketch="sjlt")
    sjlt = {svc2.submit(A, y, nu): (A, y, nu)
            for A, y, nu in draw(plan.sjlt, seed=21)}
    sols2 = svc2.flush()
    print(f"phase A flush 2 (sjlt): {len(sols2)} requests, "
          f"{time.perf_counter() - t1:.1f} s wall (incl. compile)")
    for rid, s in sols2.items():
        statuses[s.status] = statuses.get(s.status, 0) + 1
        check(s.status == "OK", f"sjlt request {rid}: status {s.status}")
        worst = max(worst, rel_err(s.x, ridge_ref(*sjlt[rid])))
    print(f"  statuses {statuses}; max relative error vs float64 host "
          f"reference {worst:.3e}")
    check(worst <= 1e-4, f"max relative error {worst:.3e} > 1e-4")

    def cls_of(group):
        return svc.bucket_for(group.n[1], group.d[1])

    B = svc.batch_size
    for group in plan.ridge:
        c = cls_of(group)
        fam = c.sketch or svc.sketch
        check_kernel(plan, f"{fam} sketch pass ({c.n}x{c.d})",
                     lambda c=c, fam=fam: sketch_pass_hlo(B, c.n, c.d,
                                                          c.m_max, fam))
    c = cls_of(plan.glm)
    check_kernel(plan, f"weighted {c.sketch or svc.sketch} sketch pass "
                 f"(GLM, {c.n}x{c.d})",
                 lambda: sketch_pass_hlo(B, c.n, c.d, c.m_max,
                                         c.sketch or svc.sketch,
                                         weighted=True))
    c = cls_of(plan.sjlt)
    check_kernel(plan, f"sjlt sketch pass ({c.n}x{c.d})",
                 lambda: sketch_pass_hlo(B, c.n, c.d, c.m_max, "sjlt"))
    print(f"phase A: {time.perf_counter() - t0:.1f} s wall (incl. compile)")


def phase_b(plan: Plan) -> None:
    """The library entry point in the paper's regime: one n × d problem
    with σ_j = rate^j and ν = 1e-2."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import padded_adaptive_solve
    from repro.core.effective_dim import exp_decay_singular_values
    from repro.core.quadratic import Quadratic
    from repro.core.status import SolveStatus

    t0 = time.perf_counter()
    n, d, nu = plan.lib_n, plan.lib_d, 1e-2
    kZ, kV, ky, ks = jax.random.split(jax.random.PRNGKey(2104), 4)
    sv = exp_decay_singular_values(d, plan.lib_rate)
    V, _ = jnp.linalg.qr(jax.random.normal(kV, (d, d)))
    A = ((jax.random.normal(kZ, (n, d)) / np.sqrt(n)) * sv) @ V.T
    y = jax.random.normal(ky, (n,))
    b = jnp.matmul(A.T, y, precision=jax.lax.Precision.HIGHEST)
    q = Quadratic(A=A, b=b, nu=jnp.asarray(nu, jnp.float32),
                  lam_diag=jnp.ones((d,), jnp.float32))
    x, stats = padded_adaptive_solve(q, ks, m_max=plan.lib_m_max,
                                     method="pcg", sketch="gaussian",
                                     max_iters=200)
    x = np.asarray(jax.block_until_ready(x))
    status = SolveStatus(int(stats["status"])).name
    t_solve = time.perf_counter() - t0
    err = rel_err(x, ridge_ref(A, y, nu))
    print(f"phase B: n={n} d={d} m_max={plan.lib_m_max} status {status}, "
          f"m_final {int(stats['m_final'])}, iters {int(stats['iters'])}, "
          f"δ̃ {float(stats['dtilde']):.3e}, relative error vs float64 host "
          f"reference {err:.3e}; {t_solve:.1f} s wall (incl. compile)")
    check(status == "OK", f"phase B: status {status}")
    check(err <= 1e-4, f"phase B: relative error {err:.3e} > 1e-4")
    check_kernel(plan, f"gaussian sketch pass ({n}x{d}, m_max "
                 f"{plan.lib_m_max})",
                 lambda: sketch_pass_hlo(1, n, d, plan.lib_m_max,
                                         "gaussian"))


def phase_four(plan: Plan) -> None:
    """The sharded service on a 4-device data mesh vs a single-device
    BlockEmulationProvider solve with the same per-shard keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.adaptive_padded import padded_adaptive_solve_batched
    from repro.core.distributed import shard_quadratic
    from repro.core.level_grams import BlockEmulationProvider
    from repro.core.quadratic import Quadratic
    from repro.serve.solver_service import SolverService

    K = 4
    check(len(jax.devices()) == K, f"--four-chips needs {K} devices, found "
          f"{len(jax.devices())}")
    t0 = time.perf_counter()
    mesh = jax.make_mesh((K,), ("data",))
    svc = SolverService(plan.sharded_classes, mesh=mesh)
    reqs = draw(plan.sharded, seed=31)
    rids = [svc.submit(A, y, nu) for A, y, nu in reqs]
    cls = svc.bucket_for(plan.sharded.n[1], plan.sharded.d[1])
    check(all(A.shape == (cls.n, cls.d) for A, _, _ in reqs),
          "sharded requests must fill the class shape exactly")
    sols = svc.flush()
    print(f"four chips: {len(sols)} requests in class n={cls.n} d={cls.d} "
          f"({cls.sketch}), {time.perf_counter() - t0:.1f} s wall "
          f"(incl. compile)")
    per_dev = {}
    for dev in jax.devices():
        st = dev.memory_stats()
        if st is not None:
            per_dev[str(dev)] = int(st.get("peak_bytes_in_use", 0))
    a_shard = cls.n // K * cls.d * 4 * svc.batch_size
    print(f"  peak bytes in use per device {per_dev}; A per device "
          f"{a_shard}")
    if plan.on_chip:
        check(len(per_dev) == K and min(per_dev.values()) >= a_shard,
              f"A is not resident on all {K} devices: {per_dev}")

    # the same problems on one device, with the shards emulated
    B = len(reqs)
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    q1 = Quadratic(
        A=jnp.stack([A for A, _, _ in reqs]),
        b=jnp.stack([jnp.matmul(A.T, y, precision=hi) for A, y, _ in reqs]),
        nu=jnp.asarray([nu for _, _, nu in reqs], f32),
        lam_diag=jnp.ones((B, cls.d), f32), batched=True)
    qd = shard_quadratic(q1, mesh)
    devs = sorted({s.device.id for s in qd.A.addressable_shards})
    shapes = {tuple(s.data.shape) for s in qd.A.addressable_shards}
    print(f"  packed A sharding {qd.A.sharding.spec} over devices {devs}, "
          f"shard shapes {shapes}")
    check(len(devs) == K and shapes == {(B, cls.n // K, cls.d)},
          "packed A is not split over all devices")
    hlo = sketch_pass_hlo(B, cls.n, cls.d, cls.m_max, cls.sketch, mesh=mesh)
    n_ar = len(re.findall(r"all-reduce(?:-start)?\(", hlo))
    print(f"  compiled sharded precompute: {n_ar} all-reduce")
    check(n_ar == 1, f"sharded precompute holds {n_ar} all-reduces, not 1")
    if plan.on_chip:
        check("tpu_custom_call" in hlo, "sharded sketch pass: no "
              "tpu_custom_call")
    del qd

    keys = jax.vmap(lambda i: jax.random.fold_in(
        jax.random.PRNGKey(0), i))(jnp.asarray(rids, jnp.uint32))
    x_emu, s_emu = padded_adaptive_solve_batched(
        q1, keys, m_max=cls.m_max, method=svc.method,
        sketch=BlockEmulationProvider(cls.sketch, K),
        max_iters=svc.max_iters, rho=svc.rho, tol=svc.tol)
    x_emu = np.asarray(x_emu)
    worst_x, ratios = 0.0, []
    for i, rid in enumerate(rids):
        s = sols[rid]
        check(s.status == "OK", f"sharded request {rid}: status {s.status}")
        worst_x = max(worst_x, rel_err(s.x, x_emu[i].astype(np.float64)))
        ratios.append(s.delta_tilde / max(float(s_emu["dtilde"][i]),
                                          1e-300))
    print(f"  sharded vs BlockEmulationProvider: max relative x difference "
          f"{worst_x:.3e}, δ̃ ratio in [{min(ratios):.3f}, "
          f"{max(ratios):.3f}]")
    check(worst_x <= 1e-5, f"sharded x differs by {worst_x:.3e} > 1e-5")
    check(all(0.5 <= r <= 2.0 for r in ratios),
          f"δ̃ ratios {ratios} outside [0.5, 2]")
    print(f"four chips: {time.perf_counter() - t0:.1f} s wall "
          f"(incl. compile)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded service path on a 4-device "
                         "mesh and its single-device comparison")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()

    import jax

    hits = {"requests": 0, "hits": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            hits["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1

    jax.monitoring.register_event_listener(count)
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; platform {dev.platform}; device_kind "
          f"{dev.device_kind!r}; {len(devices)} device(s); compile cache "
          f"{cache_dir}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 2
    try:
        if args.four_chips:
            phase_four(CHIP_PLAN)
        else:
            phase_a(CHIP_PLAN)
            phase_b(CHIP_PLAN)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compile cache: {hits['hits']} hits of {hits['requests']} "
          f"cache-eligible compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
