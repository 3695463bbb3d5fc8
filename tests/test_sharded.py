"""Sharded one-touch level-Gram providers + multi-device padded engine
(DESIGN.md §5): block-sketch normalization regression, sharded providers
vs the single-device BlockEmulationProvider reference, K=8 engine vs
single-device agreement, collective inventory (exactly one psum in the
precompute), and the serving satellites (vmapped pack keys, ν > 0 guard,
SRHT row-sampling laws).

Multi-device cases run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the test_dist.py
pattern) so the main pytest process keeps the real device view;
single-device satellites run in-process. CI additionally runs this module
as its own forced-8-device job including the slow cases.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adaptive_padded import padded_adaptive_solve_batched
from repro.core.level_grams import BlockEmulationProvider, get_provider
from repro.core.quadratic import Quadratic
from repro.core.status import SolveStatus
from repro.serve.solver_service import ShapeClass, SolverService


def _run_subprocess(code: str) -> str:
    import os
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(root / "src")}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       cwd=str(root), timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# block_sketch_gram normalization (the /√K regression)
# ---------------------------------------------------------------------------

def test_block_sketch_gram_scaling_regression():
    """E[(SA)ᵀSA] must equal AᵀA with NO per-shard rescale: per-shard
    Gaussian entries are already N(0, 1/m) and SJLT/SRHT blocks satisfy
    E[S_kᵀS_k] = I. The pre-fix /√n_shards rescale shrank the mean Gram
    to AᵀA/K (relative error ≈ (K−1)/K ≈ 0.88 at K=8, vs ≈ 0.12 for the
    corrected code at this sample count — the 0.35 threshold splits them
    decisively), and an IHS solve under the K-weak preconditioner
    overshoots its fixed 1−ρ step and diverges to NaN."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import from_least_squares, direct_solve
        from repro.core.distributed import block_sketch_gram
        from repro.core.precond import factorize
        from repro.core.solvers import run_fixed

        mesh = jax.make_mesh((8,), ("data",))
        n, d, m, R = 512, 32, 128, 16
        A = jax.random.normal(jax.random.PRNGKey(0), (n, d)) / np.sqrt(n)
        y = jax.random.normal(jax.random.PRNGKey(1), (n,))
        q = from_least_squares(A, y, 0.1)
        x_star = direct_solve(q)
        G = np.asarray(A.T @ A)

        for kind in ("gaussian", "sjlt", "srht"):
            f = jax.jit(lambda key: block_sketch_gram(A, key, kind, m, mesh))
            acc = np.zeros((d, d))
            errs = []
            for r in range(R):
                SA = f(jax.random.PRNGKey(100 + r))
                acc += np.asarray(SA.T @ SA)
                # unsharded-rate convergence: IHS's fixed 1−ρ step requires
                # a correctly scaled H_S (pre-fix it diverges to NaN/inf)
                P = factorize(SA, q.nu, q.lam_diag)
                x, trace = run_fixed(q, P, jnp.zeros((d,)), method="ihs",
                                     iters=25, rho=0.5)
                assert np.isfinite(np.asarray(trace)).all(), (kind, r)
                errs.append(float(jnp.linalg.norm(x - x_star)
                                  / jnp.linalg.norm(x_star)))
            rel = np.linalg.norm(acc / R - G) / np.linalg.norm(G)
            assert rel < 0.35, (kind, rel)   # pre-fix: ≈ 0.88
            # at m = 4d an unlucky sketch converges slowly even when
            # correctly scaled, so the 1e-3 bound is on the median over the
            # R sketches (pre-fix every solve diverges), not on one seed's
            assert np.median(errs) < 1e-3, (kind, sorted(errs))
        print("SCALING_OK")
    """)
    assert "SCALING_OK" in out


# ---------------------------------------------------------------------------
# shard_level_grams: all families vs the replicated reference
# ---------------------------------------------------------------------------

def test_shard_level_grams_match_replicated_reference():
    """For all 4 families × {per-problem, shared} A: the shard_map one-touch
    pass with fold_in(key, shard) randomness equals the single-device
    BlockEmulationProvider (identical per-shard keys), the precompute
    jaxpr lowers exactly ONE psum whose operand is the (L, B, d, d) Gram
    stack, and no global-row-count intermediate exists per shard."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.analysis.audit import collect_eqns, has_intermediate_of_shape
        from repro.core.adaptive_padded import doubling_ladder
        from repro.core.distributed import shard_level_grams, shard_quadratic
        from repro.core.level_grams import (PADDED_SKETCHES,
                                            BlockEmulationProvider,
                                            get_provider)
        from repro.core.quadratic import from_least_squares_batch

        mesh = jax.make_mesh((8,), ("data",))
        B, n, d, m_max, K = 3, 512, 8, 24, 8     # ladder has a non-pow2 cap
        ladder = doubling_ladder(m_max)
        A = jax.random.normal(jax.random.PRNGKey(0), (B, n, d)) / np.sqrt(n)
        Y = jax.random.normal(jax.random.PRNGKey(1), (B, n))
        keys = jax.random.split(jax.random.PRNGKey(42), B)
        q_per = from_least_squares_batch(A, Y, jnp.asarray([0.1, 0.2, 0.3]))
        q_sh = from_least_squares_batch(A[0], Y, 0.1)
        assert q_sh.shared_A and not q_per.shared_A

        for sketch in PADDED_SKETCHES:
            prov = get_provider(sketch)
            emu = BlockEmulationProvider(sketch, K)
            for q in (q_per, q_sh):
                got = np.asarray(shard_level_grams(prov, keys, q, ladder,
                                                   mesh))
                want = np.asarray(emu.level_grams(
                    emu.sample(keys, m_max, q.n, jnp.float32), q, ladder))
                rel = (np.linalg.norm(got - want)
                       / (np.linalg.norm(want) + 1e-30))
                assert rel < 1e-5, (sketch, q.shared_A, rel)

                jx = jax.make_jaxpr(
                    lambda q, ks: shard_level_grams(prov, ks, q, ladder,
                                                    mesh))(q, keys)
                ps = collect_eqns(jx, "psum")
                assert len(ps) == 1, (sketch, len(ps))
                L = len(ladder)
                assert tuple(ps[0].outvars[0].aval.shape) == (L, B, d, d)
                # the communicated payload is the Gram stack, and no GLOBAL
                # dense sketch (B, m_max, n) exists anywhere; the streamed
                # family never materializes even the LOCAL dense sketch
                assert not has_intermediate_of_shape(jx, (B, m_max, n))
                if sketch == "gaussian":
                    assert not has_intermediate_of_shape(
                        jx, (B, m_max, n // K))

            # per-shard key independence: distinct shards draw distinct
            # randomness (fold_in(key, k)), so their partial Grams differ
            sh = emu.sample(keys, m_max, n, jnp.float32)["shards"]
            g0 = np.asarray(get_provider(sketch).level_grams(
                sh[0], from_least_squares_batch(
                    A[:, : n // K], Y[:, : n // K],
                    jnp.asarray([0.1, 0.2, 0.3])), ladder))
            g1 = np.asarray(get_provider(sketch).level_grams(
                sh[1], from_least_squares_batch(
                    A[:, : n // K], Y[:, : n // K],
                    jnp.asarray([0.1, 0.2, 0.3])), ladder))
            assert not np.allclose(g0, g1), sketch
        print("PROVIDERS_OK")
    """)
    assert "PROVIDERS_OK" in out


def test_weighted_shard_level_grams_and_gram():
    """GLM-layer sharded path (DESIGN.md §8): with row_weights the one-psum
    ladder precompute equals the weighted BlockEmulationProvider (identical
    per-shard keys — W is row-diagonal, so it splits over row blocks
    exactly like A), shard_weighted_gram psums to AᵀWA, and a weighted
    sharded engine solve matches the single-device weighted solve."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.adaptive_padded import (doubling_ladder,
                                                padded_adaptive_solve_batched)
        from repro.core.distributed import (shard_level_grams,
                                            shard_quadratic,
                                            shard_weighted_gram)
        from repro.core.level_grams import BlockEmulationProvider, get_provider
        from repro.core.quadratic import direct_solve, from_least_squares_batch

        mesh = jax.make_mesh((8,), ("data",))
        B, n, d, m_max, K = 3, 512, 8, 24, 8
        ladder = doubling_ladder(m_max)
        A = jax.random.normal(jax.random.PRNGKey(0), (B, n, d)) / np.sqrt(n)
        Y = jax.random.normal(jax.random.PRNGKey(1), (B, n))
        w = jax.random.uniform(jax.random.PRNGKey(2), (B, n),
                               minval=0.05, maxval=2.0)
        keys = jax.random.split(jax.random.PRNGKey(42), B)
        qw = from_least_squares_batch(A, Y, jnp.asarray([0.1, 0.2, 0.3])
                                      ).with_row_weights(w)
        qd = shard_quadratic(qw, mesh)
        for sketch in ("gaussian", "sjlt", "srht"):
            got = np.asarray(shard_level_grams(get_provider(sketch), keys,
                                               qd, ladder, mesh))
            emu = BlockEmulationProvider(sketch, K)
            want = np.asarray(emu.level_grams(
                emu.sample(keys, m_max, n, jnp.float32), qw, ladder))
            rel = np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30)
            assert rel < 1e-5, (sketch, rel)
        G = np.asarray(shard_weighted_gram(qd, mesh))
        G_ref = np.asarray(jnp.einsum("bn,bnd,bne->bde", w, A, A))
        assert np.linalg.norm(G - G_ref) / np.linalg.norm(G_ref) < 1e-5
        x_sh, s_sh = padded_adaptive_solve_batched(
            qd, keys, m_max=m_max, method="pcg", sketch="gaussian",
            max_iters=100, tol=1e-12, mesh=mesh)
        x_star = np.asarray(direct_solve(qw))
        rel = np.linalg.norm(np.asarray(x_sh) - x_star) / np.linalg.norm(x_star)
        assert rel < 1e-4, rel
        print("WEIGHTED_SHARDED_OK")
    """)
    assert "WEIGHTED_SHARDED_OK" in out


# ---------------------------------------------------------------------------
# K=8 engine vs single device (acceptance)
# ---------------------------------------------------------------------------

def test_sharded_engine_matches_single_device():
    """The sharded engine on a K=8 mesh agrees with single-device solves:
    x to ≤1e-5 against BOTH the plain single-device engine (different
    sketch law, same optimum) and the BlockEmulationProvider run
    (identical per-shard keys — certificates δ̃ within 2×, schedules in
    fact identical)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.adaptive_padded import padded_adaptive_solve_batched
        from repro.core.distributed import sharded_padded_solve
        from repro.core.level_grams import BlockEmulationProvider
        from repro.core.quadratic import direct_solve, from_least_squares_batch

        mesh = jax.make_mesh((8,), ("data",))
        B, n, d, m_max = 4, 512, 16, 64
        A = jax.random.normal(jax.random.PRNGKey(0), (B, n, d)) / np.sqrt(n)
        Y = jax.random.normal(jax.random.PRNGKey(1), (B, n))
        q = from_least_squares_batch(A, Y, jnp.asarray([0.3, 0.4, 0.5, 0.6]))
        keys = jax.random.split(jax.random.PRNGKey(42), B)
        emu = BlockEmulationProvider("gaussian", 8)
        rel = lambda a, b: float(jnp.linalg.norm(a - b)
                                 / (jnp.linalg.norm(b) + 1e-30))

        # deep convergence (floor-polish): x agreement across all three
        kw = dict(m_max=m_max, method="pcg", tol=1e-12, max_iters=200)
        x_sh, _ = sharded_padded_solve(q, keys, mesh, sketch="gaussian",
                                       **kw)
        x_1, _ = padded_adaptive_solve_batched(q, keys, sketch="gaussian",
                                               **kw)
        x_emu, _ = padded_adaptive_solve_batched(q, keys, sketch=emu, **kw)
        X = direct_solve(q)
        for i in range(B):
            assert rel(x_sh[i], x_1[i]) <= 1e-5, i
            assert rel(x_sh[i], x_emu[i]) <= 1e-5, i
            assert rel(x_sh[i], X[i]) <= 1e-4, i

        # certificate agreement where δ̃ is set by the stopping rule, not
        # f32 floor noise: identical per-shard keys ⇒ identical trajectories
        # (same doubling schedules, δ̃ within 2× — in practice within fp)
        kw = dict(m_max=m_max, method="pcg", tol=1e-8, max_iters=200)
        _, s_sh = sharded_padded_solve(q, keys, mesh, sketch="gaussian",
                                       **kw)
        _, s_emu = padded_adaptive_solve_batched(q, keys, sketch=emu, **kw)
        for i in range(B):
            ratio = float(s_sh["dtilde"][i]) / max(float(s_emu["dtilde"][i]),
                                                   1e-300)
            assert 0.5 <= ratio <= 2.0, (i, ratio)
        assert np.array_equal(np.asarray(s_sh["m_final"]),
                              np.asarray(s_emu["m_final"]))
        # at m_max < d one device takes the dual ladder form; the mesh
        # keeps the primal form, whose psum is over d×d level Grams
        kw = dict(m_max=8, method="pcg", tol=1e-12, max_iters=200)
        x_sh, s_sh = sharded_padded_solve(q, keys, mesh, sketch="gaussian",
                                          **kw)
        x_1, s_1 = padded_adaptive_solve_batched(q, keys, sketch="gaussian",
                                                 **kw)
        assert not np.asarray(s_sh["ladder_dual"]).any()
        assert np.asarray(s_1["ladder_dual"]).all()
        for i in range(B):
            assert rel(x_sh[i], X[i]) <= 1e-4, i
            assert rel(x_1[i], X[i]) <= 1e-4, i
        print("ENGINE_OK")
    """)
    assert "ENGINE_OK" in out


@pytest.mark.slow
def test_sharded_solver_service_end_to_end():
    """SolverService(mesh=...) solves real requests on an 8-device mesh and
    matches the dense direct solve; slot utilization is reported."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import direct_solve, from_least_squares
        from repro.serve.solver_service import ShapeClass, SolverService

        mesh = jax.make_mesh((8,), ("data",))
        svc = SolverService(batch_size=4, sketch="gaussian", tol=1e-12,
                            mesh=mesh,
                            shape_classes=(ShapeClass(256, 32, 64),
                                           ShapeClass(1024, 64, 128)))
        rng = np.random.default_rng(0)
        reqs = []
        for i in range(5):
            n = int(rng.integers(64, 900))
            d = int(rng.integers(8, 60))
            A = jax.random.normal(jax.random.PRNGKey(i), (n, d)) / np.sqrt(n)
            y = jax.random.normal(jax.random.PRNGKey(50 + i), (n,))
            nu = float(rng.uniform(0.1, 0.4))
            reqs.append((svc.submit(A, y, nu), A, y, nu))
        sols = svc.flush()
        assert len(sols) == 5
        for rid, A, y, nu in reqs:
            s = sols[rid]
            x_star = direct_solve(from_least_squares(A, y, nu))
            r = float(jnp.linalg.norm(s.x - x_star)
                      / jnp.linalg.norm(x_star))
            assert r < 1e-4, (rid, r)
        assert 0.0 < svc.slot_utilization() <= 1.0
        print("SERVICE_OK", svc.slot_utilization())
    """)
    assert "SERVICE_OK" in out


# ---------------------------------------------------------------------------
# In-process satellites (single device)
# ---------------------------------------------------------------------------

def test_srht_row_sampling_laws():
    """ops.srht_sketch samples rows WITHOUT replacement (classical SRHT:
    m = n_pad gives all-distinct rows), while SRHTProvider's ladder stream
    is i.i.d. WITH replacement (duplicates near-certain at m_max = n_pad) —
    the documented difference both docstrings pin."""
    from repro.kernels import ops

    n = 60                                   # n_pad = 64
    n_pad = 64
    I = jnp.eye(n, dtype=jnp.float32)
    S = np.asarray(ops.srht_sketch(I, jax.random.PRNGKey(0), n_pad))
    # distinct Hadamard rows (same sign diagonal) → pairwise distinct rows
    uniq = np.unique(np.round(S, 5), axis=0)
    assert uniq.shape[0] == n_pad, uniq.shape

    prov = get_provider("srht")
    dup = 0
    for seed in range(5):
        keys = jax.random.split(jax.random.PRNGKey(seed), 1)
        rows = np.asarray(prov.sample(keys, n_pad, n, jnp.float32)["rows"])[0]
        assert rows.shape == (n_pad,)
        dup += int(len(np.unique(rows)) < n_pad)
    assert dup == 5, "i.i.d. row stream should collide at m_max = n_pad"


def test_service_rejects_nu_zero():
    """ν = 0 padded problems NaN-poison certificates inside the pre-guard
    engine (demonstrated with guards=False); the DESIGN.md §9 guards turn
    that into a finite iterate with a truthful LEVEL_INVALID verdict, and
    SolverService.submit still rejects ν = 0 up front so neither failure
    shape reaches flush."""
    # the guarded failure: zero-padded coordinate + ν = 0 ⇒ H_S singular
    n, d = 32, 4
    A = np.array(jax.random.normal(jax.random.PRNGKey(0), (1, n, d)),
                 np.float32)
    A[:, :, -1] = 0.0                        # a padded (all-zero) column
    b = np.zeros((1, d), np.float32)
    b[0, :d - 1] = 1.0
    q = Quadratic(A=jnp.asarray(A), b=jnp.asarray(b),
                  nu=jnp.zeros((1,)), lam_diag=jnp.ones((1, d)),
                  batched=True)
    _, stats = padded_adaptive_solve_batched(
        q, jax.random.PRNGKey(1), m_max=8, method="pcg", guards=False)
    assert not np.isfinite(np.asarray(stats["dtilde"])).all()
    x_g, stats_g = padded_adaptive_solve_batched(
        q, jax.random.PRNGKey(1), m_max=8, method="pcg")
    assert np.isfinite(np.asarray(x_g)).all()
    assert np.asarray(stats_g["status"])[0] == int(SolveStatus.LEVEL_INVALID)

    svc = SolverService(shape_classes=(ShapeClass(64, 8, 16),), batch_size=2)
    A1 = jnp.ones((32, 4)) / 8.0
    y1 = jnp.ones((32,))
    for bad in (0.0, -1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError):
            svc.submit(A1, y1, bad)
    rid = svc.submit(A1, y1, 0.5)            # valid request still flows
    sol = svc.flush()[rid]
    assert np.isfinite(sol.delta_tilde)
    assert np.isfinite(np.asarray(sol.x)).all()


def test_pack_vmapped_keys_and_padded_slots():
    """_pack computes all slot keys in ONE vmapped fold_in: real slot i
    carries fold_in(base, req_id); padded slot s carries the reserved
    top-of-range fold_in(base, 2³²−1−s) — all B keys pairwise distinct, so
    a padded slot can never alias a real request's sketch."""
    svc = SolverService(shape_classes=(ShapeClass(64, 8, 16),), batch_size=4)
    for _ in range(2):
        svc.submit(jnp.ones((32, 4)) / 8.0, jnp.ones((32,)), 0.3)
    cls = svc.shape_classes[0]
    reqs = svc._queues[cls]
    q, keys = svc._pack(cls, reqs)
    keys = np.asarray(keys)
    assert keys.shape[0] == 4
    for i, r in enumerate(reqs):
        want = np.asarray(jax.random.fold_in(svc._base_key, r.req_id))
        np.testing.assert_array_equal(keys[i], want)
    for s in (2, 3):
        want = np.asarray(jax.random.fold_in(svc._base_key, 2**32 - 1 - s))
        np.testing.assert_array_equal(keys[s], want)
    flat = [tuple(k.ravel().tolist()) for k in keys]
    assert len(set(flat)) == 4


def test_block_emulation_provider_single_device():
    """The emulation provider is the replicated reference: K=2 shard sum
    over row halves with folded keys, for every family; get_provider
    passes instances through; non-divisible n is rejected."""
    from repro.core.quadratic import from_least_squares_batch

    B, n, d, m_max = 2, 64, 4, 8
    A = jax.random.normal(jax.random.PRNGKey(0), (B, n, d))
    Y = jax.random.normal(jax.random.PRNGKey(1), (B, n))
    q = from_least_squares_batch(A, Y, 0.1)
    keys = jax.random.split(jax.random.PRNGKey(2), B)
    ladder = (1, 2, 4, 8)
    for sketch in ("gaussian", "sjlt", "srht"):
        emu = BlockEmulationProvider(sketch, 2)
        assert get_provider(emu) is emu
        got = np.asarray(emu.level_grams(
            emu.sample(keys, m_max, n, jnp.float32), q, ladder))
        inner = get_provider(sketch)
        want = 0
        for k in range(2):
            fk = jax.vmap(lambda kb: jax.random.fold_in(kb, k))(keys)
            qk = from_least_squares_batch(
                A[:, k * (n // 2):(k + 1) * (n // 2)],
                Y[:, k * (n // 2):(k + 1) * (n // 2)], 0.1)
            want = want + np.asarray(inner.level_grams(
                inner.sample(fk, m_max, n // 2, jnp.float32), qk, ladder))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=sketch)
    with pytest.raises(ValueError):
        BlockEmulationProvider("gaussian", 2).sample(keys, m_max, 63,
                                                     jnp.float32)


def test_pod_scale_class_gated_on_mesh():
    """The n=65536 tail class is only a default for sharded services: a
    mesh-less service keeps failing fast on requests no device can hold,
    while SolverService(mesh=...) buckets them."""
    svc = SolverService()
    assert max(c.n for c in svc.shape_classes) == 16384
    with pytest.raises(ValueError):
        svc.bucket_for(20000, 64)
    mesh = jax.make_mesh((1,), ("data",))
    svc_sh = SolverService(mesh=mesh)
    assert svc_sh.bucket_for(20000, 64).n == 65536


def test_ridge_flags():
    """--ridge-batch is its own flag (default 16, not the LM --batch=4)
    and --mesh selects the data-shard count."""
    from repro.launch.serve import build_parser

    ap = build_parser()
    args = ap.parse_args(["--ridge"])
    assert args.ridge_batch == 16 and args.mesh == 0 and args.batch == 4
    args = ap.parse_args(["--ridge", "--ridge-batch", "8", "--mesh", "4"])
    assert args.ridge_batch == 8 and args.mesh == 4
