"""Batched serving launcher: load (or init) a model, prefill a batch of
prompts, stream greedy continuations. CPU-scale here; the pod launch uses
the same decode_step under the production mesh (see launch/dryrun.py
decode cells for the compiled configuration).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced

``--ridge`` serves the other production workload instead: a stream of
heterogeneous ridge-solve requests, bucketed into shape classes and solved
in fixed-shape batches by the multi-problem adaptive engine
(serve/solver_service.py, DESIGN.md §6):

    PYTHONPATH=src python -m repro.launch.serve --ridge --requests 64 \
        --ridge-batch 16 [--sketch srht] [--dtype bf16] [--mesh 8] [--glm 16]

(``--ridge-batch`` sizes the packed solver batches; ``--mesh K`` runs the
sharded engine over a K-device data mesh — see DESIGN.md §5; ``--glm N``
adds N logistic requests served by the adaptive sketched-Newton driver
with Newton-level certificates — DESIGN.md §8; ``--dtype bf16``/``int8``
runs the one-touch sketch pass at reduced stream precision with fp32
certificates — DESIGN.md §10; ``--deadline-s T`` bounds the flush —
expired requests return DEADLINE_EXCEEDED with their best finite iterate
— DESIGN.md §11; ``--path N`` adds N regularization-path requests, each a
``--path-points``-long λ grid solved off ONE one-touch sketch pass with
warm-started per-λ solves, plus a repeated-A round served entirely from
the fingerprint ladder cache — DESIGN.md §13.)

``--preempt-after N`` drives the preemption chaos cycle instead (DESIGN.md
§11): launch ``examples/solve_service.py`` as a checkpointing subprocess,
SIGTERM it N seconds into the flush, assert it exits 75 after committing
its solver state, restart it with ``--resume``, and assert every request
terminates finite with an honest status:

    PYTHONPATH=src python -m repro.launch.serve --preempt-after 3
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.ft import CheckpointManager
from repro.launch.runtime import enable_compile_cache
from repro.models import init_params
from repro.serve.step import greedy_generate


def serve_ridge(args):
    """Ridge-solve serving demo: random-shape requests through the
    shape-class bucketing + batched adaptive engine. ``--mesh K`` places
    each packed batch's A row-sharded over a K-device data mesh (run under
    XLA_FLAGS=--xla_force_host_platform_device_count=K to demo on CPU)."""
    import numpy as np

    from repro.serve.solver_service import SolverService

    mesh = None
    if args.mesh:
        if args.mesh > jax.device_count():
            raise SystemExit(
                f"--mesh {args.mesh} needs {args.mesh} devices but only "
                f"{jax.device_count()} exist; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.mesh}")
        mesh = jax.make_mesh((args.mesh,), ("data",))
    from repro.serve.solver_service import GLMSolution, PathSolution

    svc = SolverService(batch_size=args.ridge_batch, method="pcg",
                        sketch=args.sketch, compute_dtype=args.dtype,
                        mesh=mesh, strict=not args.faulty,
                        ladder_cache=bool(args.path))
    rng = np.random.default_rng(0)
    truth = {}
    for i in range(args.requests):
        n = int(rng.integers(64, 1800))
        d = int(rng.integers(8, 120))
        A = jax.random.normal(jax.random.PRNGKey(2 * i), (n, d)) / np.sqrt(n)
        y = jax.random.normal(jax.random.PRNGKey(2 * i + 1), (n,))
        rid = svc.submit(A, y, nu=float(rng.uniform(0.05, 0.5)))
        truth[rid] = (A, y)
    for i in range(args.faulty):
        # quarantine-path demo: NaN-poisoned requests ride the same flush
        # and come back REJECTED without touching their packed neighbors
        A = jnp.full((128, 16), jnp.nan)
        svc.submit(A, jnp.zeros(128), nu=0.1)
    from repro.core.objectives import synthetic_logistic_problem

    for i in range(args.glm):
        n = int(rng.integers(64, 1800))
        d = int(rng.integers(8, 120))
        A, y = synthetic_logistic_problem(jax.random.PRNGKey(10_000 + i),
                                          n, d)
        svc.submit_glm(A, y, nu=float(rng.uniform(0.1, 0.5)),
                       family="logistic")
    path_truth = {}
    for i in range(args.path):
        # regularization-path traffic (DESIGN.md §13): each request is a λ
        # GRID solved off ONE one-touch sketch pass, strong→weak so warm
        # starts move downhill
        n = int(rng.integers(64, 1800))
        d = int(rng.integers(8, 120))
        A = jax.random.normal(
            jax.random.PRNGKey(20_000 + 2 * i), (n, d)) / np.sqrt(n)
        y = jax.random.normal(jax.random.PRNGKey(20_001 + 2 * i), (n,))
        nus = np.geomspace(1.0, 1e-2, args.path_points)
        rid = svc.submit_path(A, y, nus)
        path_truth[rid] = (A, y, nus)
    t0 = time.perf_counter()
    sols = svc.flush(deadline_s=args.deadline_s)
    dt = time.perf_counter() - t0
    if not sols:
        print("ridge service: no requests")
        return
    ridge_sols = [s for s in sols.values()
                  if not isinstance(s, (GLMSolution, PathSolution))]
    glm_sols = [s for s in sols.values() if isinstance(s, GLMSolution)]
    path_sols = [s for s in sols.values() if isinstance(s, PathSolution)]
    n_req = args.requests + args.glm + args.path
    mesh_note = f", {args.mesh}-way data mesh" if mesh is not None else ""
    print(f"solver service: {n_req} requests in {dt:.2f}s "
          f"({n_req / dt:.1f} req/s incl. compile) — "
          f"{svc.stats['batches']} batches of {svc.batch_size}, "
          f"{svc.stats['padded_slots']} padded slots "
          f"({100 * svc.slot_utilization():.0f}% slot utilization"
          f"{mesh_note})")
    # only converged solutions carry a trustworthy δ̃ certificate; rejected /
    # fallen-back / expired ones report NaN there by design
    ridge_ok = [s for s in ridge_sols if s.converged]
    if ridge_ok:
        m_finals = [s.m_final for s in ridge_ok]
        fams = sorted({s.sketch for s in ridge_ok})
        dts = sorted({s.compute_dtype for s in ridge_ok})
        print(f"ridge certificates ({'/'.join(fams)}, "
              f"dtype {'/'.join(dts)}): "
              f"m_final min/median/max = "
              f"{min(m_finals)}/{sorted(m_finals)[len(m_finals) // 2]}/"
              f"{max(m_finals)}, "
              f"max residual δ̃ = {max(s.delta_tilde for s in ridge_ok):.2e}")
    # failure-model report (DESIGN.md §9): every request has a verdict
    counts: dict[str, int] = {}
    for s in sols.values():
        counts[s.status] = counts.get(s.status, 0) + 1
    verdicts = ", ".join(f"{k}={v}" for k, v in sorted(counts.items()))
    print(f"statuses: {verdicts}; retries={svc.stats['retries']}, "
          f"fallbacks={svc.stats['fallbacks']}, "
          f"rejected={svc.stats['rejected']}, "
          f"deadline_exceeded={svc.stats['deadline_exceeded']}")
    if glm_sols:
        outer = [s.newton_iters for s in glm_sols]
        print(f"glm certificates (logistic): "
              f"{sum(s.converged for s in glm_sols)}/{len(glm_sols)} "
              f"converged, outer iters min/max = {min(outer)}/{max(outer)}, "
              f"max decrement λ̃²/2 = "
              f"{max(s.decrement for s in glm_sols):.2e}, "
              f"m trajectory (req {glm_sols[0].req_id}): "
              f"{glm_sols[0].m_trajectory}")
    if path_sols:
        pts = [p for s in path_sols for p in s.points]
        passes = sum(s.sketch_passes for s in path_sols)
        s0 = path_sols[0]
        print(f"path certificates: {sum(s.converged for s in path_sols)}/"
              f"{len(path_sols)} grids converged "
              f"({args.path_points} λ points each), "
              f"{passes} one-touch passes total, "
              f"max δ̃ = {max(p.delta_tilde for p in pts):.2e}, "
              f"warm m trajectory (req {s0.req_id}): "
              f"{tuple(p.m_final for p in s0.points)}")
        # repeated-A round: the λ-free ladder is keyed by content
        # fingerprint, so the re-submitted grid never touches A again
        rid0 = min(path_truth)
        A, y, nus = path_truth[rid0]
        rid_warm = svc.submit_path(A, y, nus)
        warm = svc.flush()[rid_warm]
        print(f"repeat-A path round: cache_hit={warm.cache_hit}, "
              f"sketch_passes={warm.sketch_passes} "
              f"(ladder served from the fingerprint cache; "
              f"{svc.stats['sketch_passes_saved']} passes saved)")


def serve_preempt(args):
    """The kill → restart serving story, end to end (DESIGN.md §11):
    run the checkpointing ridge demo as a subprocess, SIGTERM it
    ``--preempt-after`` seconds in, restart with ``--resume``, and verify
    every request still terminates finite with a truthful status.

    The child needs the accelerator, and a chip belongs to one process:
    this parent must not initialize a JAX backend (no array, no device
    query) before ``Popen`` — only config updates happen before it."""
    import os
    import shutil
    import signal
    import subprocess
    import sys
    import tempfile

    root = Path(__file__).resolve().parents[3]
    ck = tempfile.mkdtemp(prefix="preempt_ck_")
    env = {**os.environ,
           "PYTHONPATH": os.pathsep.join(
               [str(root / "src")]
               + ([os.environ["PYTHONPATH"]]
                  if os.environ.get("PYTHONPATH") else []))}
    # tol=0 + bounded iters + no fallback keeps the flush long enough for
    # the signal to land mid-solve, while still terminating on restart
    cmd = [sys.executable, "-u", str(root / "examples" / "solve_service.py"),
           "--requests", "6", "--tol", "0", "--max-iters", "1200",
           "--max-retries", "0", "--no-fallback", "--segment-trips", "16",
           "--checkpoint-dir", ck]
    try:
        print(f"preemption cycle: checkpoints in {ck}")
        p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True)
        time.sleep(args.preempt_after)
        p.send_signal(signal.SIGTERM)
        out, _ = p.communicate(timeout=600)
        print(out, end="")
        if p.returncode == 0:
            print("note: flush finished before the SIGTERM landed; "
                  "restart will resume-from-complete")
        elif p.returncode != 75:
            raise SystemExit(
                f"preempted service exited {p.returncode}, expected 75")
        r = subprocess.run(cmd + ["--resume"], env=env,
                           capture_output=True, text=True, timeout=600)
        print(r.stdout, end="")
        if r.returncode != 0:
            raise SystemExit(
                f"resumed service exited {r.returncode}:\n"
                f"{r.stderr[-2000:]}")
        if "ALL_FINITE=1" not in r.stdout:
            raise SystemExit("resumed service returned non-finite answers")
        print("preemption cycle OK: SIGTERM → exit 75 → --resume → "
              "all requests finite with honest statuses")
    finally:
        shutil.rmtree(ck, ignore_errors=True)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4,
                    help="LM-decode batch size (NOT the ridge batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ckpt-dir", default="",
                    help="restore params from a training checkpoint")
    ap.add_argument("--ridge", action="store_true",
                    help="serve ridge-solve requests instead of LM decode")
    ap.add_argument("--requests", type=int, default=48,
                    help="number of synthetic ridge requests (--ridge)")
    ap.add_argument("--glm", type=int, default=0,
                    help="additionally serve this many synthetic logistic "
                         "requests through the sketched-Newton path "
                         "(--ridge; certificates include outer iterations, "
                         "Newton decrement and the m trajectory)")
    ap.add_argument("--path", type=int, default=0,
                    help="additionally serve this many regularization-path "
                         "requests (--ridge): each is a λ grid solved off "
                         "ONE one-touch sketch pass with warm-started "
                         "per-λ solves; also runs a repeated-A round "
                         "served from the fingerprint ladder cache "
                         "(DESIGN.md §13)")
    ap.add_argument("--path-points", type=int, default=8,
                    help="λ points per path request (--path), geomspace "
                         "1.0 → 1e-2 strong→weak")
    ap.add_argument("--ridge-batch", type=int, default=16,
                    help="packed batch size per shape class (--ridge); "
                         "its own flag so the LM --batch default of 4 "
                         "cannot silently leave 3/4 of the slots padded")
    ap.add_argument("--faulty", type=int, default=0,
                    help="additionally submit this many NaN-poisoned ridge "
                         "requests (--ridge); runs the service with "
                         "strict=False so they exercise the quarantine → "
                         "REJECTED path instead of raising at submit")
    ap.add_argument("--mesh", type=int, default=0,
                    help="row-shard each packed batch's A over this many "
                         "data-mesh devices (--ridge); 0 = single device")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="wall-clock budget for the ridge flush (--ridge); "
                         "expired requests return DEADLINE_EXCEEDED with "
                         "their best finite iterate (DESIGN.md §11)")
    ap.add_argument("--preempt-after", type=float, default=0.0,
                    help="run the preemption chaos cycle instead: SIGTERM "
                         "the checkpointing ridge demo this many seconds "
                         "into its flush, then restart it with --resume "
                         "and verify finite, honest results")
    from repro.core.level_grams import COMPUTE_DTYPES, PADDED_SKETCHES

    ap.add_argument("--sketch", default="gaussian",
                    choices=PADDED_SKETCHES,
                    help="sketch family for the ridge service (--ridge)")
    ap.add_argument("--dtype", default="fp32", choices=COMPUTE_DTYPES,
                    help="sketch-pass compute dtype for the ridge service "
                         "(--ridge): bf16 streams/contracts sketch operands "
                         "in bfloat16 with fp32 accumulation, int8 "
                         "additionally quantizes A per row; certificates "
                         "stay fp32 and record the mode (DESIGN.md §10)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    if args.preempt_after:
        return serve_preempt(args)
    if args.ridge:
        return serve_ridge(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    max_seq = args.prompt_len + args.new_tokens + 1
    params = init_params(jax.random.PRNGKey(0), cfg, max_seq=max_seq)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        (params, _), _ = mgr.restore((params, None))

    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab
    )
    enc = None
    if cfg.n_enc_layers:
        enc = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.enc_seq, cfg.d_model)
        )
    t0 = time.perf_counter()
    out = greedy_generate(params, cfg, prompts, args.new_tokens,
                          max_seq=max_seq, enc_feats=enc)
    dt = time.perf_counter() - t0
    print(f"{cfg.name}: {args.batch}×{args.new_tokens} tokens in {dt:.2f}s "
          f"({args.batch*args.new_tokens/dt:.1f} tok/s)")
    print("ids:", out[0].tolist())


if __name__ == "__main__":
    main()
