"""Ridge-solve serving path on top of the batched padded engine.

Production traffic is many *small heterogeneous* ridge problems (per-user /
per-tenant heads, per-λ sweeps, one-hot class blocks), not one big solve.
A fixed-shape accelerator executable cannot chase every (n, d): instead the
service

1. **buckets** each request into a fixed (n, d, m_max) *shape class* — the
   smallest configured class that fits; A is zero-padded to (n_c, d_c) with
   Λ = 1 on padded coordinates, which block-diagonalizes H so the padded
   solution restricted to the original coordinates is EXACTLY the original
   solution (padded coords solve ν²x = 0 ⇒ 0);
2. **packs** up to ``batch_size`` requests per class into one batched
   ``Quadratic`` (padding short batches with trivial b = 0 problems that
   converge at initialization);
3. **solves** the batch in one call of the fully-jitted multi-problem
   adaptive engine (``core.adaptive_padded``) — per-problem doubling, one
   executable per shape class, with a per-class ``sketch=`` family
   (streamed gaussian / sjlt / srht; the streaming providers keep the
   precompute at O(B·d²·L) live bytes, which is what lets large-n shape
   classes exist at all);
4. **returns** per-request solutions with their adaptivity *certificates*
   (δ̃, m_final, iterations, doublings) so callers can audit convergence.

GLM traffic (DESIGN.md §8): ``submit_glm`` takes the same (A, y, ν) with a
``family`` — logistic / poisson / huber — and rides the SAME shape-class /
packing machinery; a packed GLM batch is solved by the adaptive sketched-
Newton driver (``core.newton``), whose inner weighted subproblems run on
the padded engine with per-problem warm-started sketch ladders. Solutions
carry Newton-level certificates: outer iterations, the final Newton
decrement λ̃²/2, and the per-step m trajectory.

Path traffic (DESIGN.md §13): ``submit_path`` takes (A, y, a λ GRID) and
returns one ``PathSolution`` whose per-λ ``PathPoint``s each carry the
full δ̃/m/status certificate. A packed path chunk runs
``core.robust.robust_path_solve_batched``: ONE one-touch sketch pass
serves the whole grid (the ladder-level Grams are λ-free; the ν²Λ shift
enters at factorization), with x and the per-problem sketch level
warm-started point-to-point.

Ladder cache (opt-in ``ladder_cache=True``): the λ-free ladder is ALSO
reusable across *requests* that share (A, Λ, sketch family,
compute_dtype). The service fingerprints that identity, keys each slot's
sketch off the fingerprint instead of the request id (identical data ⇒
identical sketch ⇒ the cached per-slot ladder slice is exactly what the
pass would recompute), and serves warm repeated-A traffic — per-tenant
heads, λ re-sweeps — without touching A at all. Solutions record
``cache_hit``; the first slice of the continuous-batching roadmap item.

CPU-scale demo wiring lives in ``launch/serve.py --ridge`` (plus ``--glm``)
and ``examples/solve_service.py``; the batched-vs-looped engine comparison
is ``benchmarks/bench_batched.py``. See DESIGN.md §6/§8.
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import OrderedDict
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.adaptive_padded import doubling_ladder, prepare_path_ladder
from repro.core.distributed import n_data_shards, shard_quadratic
from repro.core.newton import adaptive_newton_solve_batched
from repro.core.objectives import get_objective
from repro.core.quadratic import Quadratic
from repro.core.robust import (
    robust_padded_solve_batched,
    robust_path_solve_batched,
)
from repro.core.status import SolveStatus, status_name


class ShapeClass(NamedTuple):
    n: int       # padded row count
    d: int       # padded feature count
    m_max: int   # padded sketch budget for the class
    sketch: str | None = None   # per-class sketch family (None → service
                                # default): large-n classes pick ``srht``
                                # (one FWHT pass) or keep the streamed
                                # ``gaussian`` — both run in O(B·d²·L) live
                                # memory, where the old dense Gaussian
                                # needed O(B·m_max·n) and could not hold
                                # these shapes
    compute_dtype: str | None = None  # per-class sketch-pass precision
                                # (None → service default): "bf16" halves
                                # the large-n classes' stream bandwidth,
                                # "int8" serves quantized features
                                # (kernels.precision); certificates stay
                                # fp32 and record the mode used


DEFAULT_SHAPE_CLASSES = (
    ShapeClass(n=256, d=32, m_max=64),
    ShapeClass(n=1024, d=64, m_max=128),
    ShapeClass(n=2048, d=128, m_max=256),
    ShapeClass(n=4096, d=256, m_max=512),
    # large-n tail: viable only with streaming sketch→Gram providers
    ShapeClass(n=16384, d=256, m_max=512, sketch="srht"),
)

# Sharded services (mesh=...) additionally serve the pod-scale tail: a
# single device cannot hold the packed (B, n, d) batch at n=65536, but
# each data shard only sees n/K rows and the one-touch pass psums the
# (L, B, d, d) level Grams (DESIGN.md §5). This is the default for
# SolverService(mesh=...); a mesh-less service keeps rejecting such
# requests with the clear "no shape class fits" error.
SHARDED_SHAPE_CLASSES = DEFAULT_SHAPE_CLASSES + (
    ShapeClass(n=65536, d=256, m_max=512, sketch="srht"),
)


@dataclasses.dataclass(frozen=True)
class RidgeRequest:
    req_id: int
    A: jnp.ndarray           # (n, d) features
    y: jnp.ndarray           # (n,) targets
    nu: float                # regularization ν
    lam_diag: jnp.ndarray | None = None
    deadline: float | None = None   # absolute time.perf_counter() stamp


@dataclasses.dataclass(frozen=True)
class PathRequest:
    req_id: int
    A: jnp.ndarray           # (n, d) features
    y: jnp.ndarray           # (n,) targets
    nus: tuple               # λ grid (ν values), walked in order — sort
                             # strong→weak so warm starts move downhill
    lam_diag: jnp.ndarray | None = None
    deadline: float | None = None   # absolute time.perf_counter() stamp


@dataclasses.dataclass(frozen=True)
class GLMRequest:
    req_id: int
    A: jnp.ndarray           # (n, d) features
    y: jnp.ndarray           # (n,) targets (labels / counts / responses)
    nu: float                # regularization ν
    family: str              # "logistic" | "poisson" | "huber[:delta]"
    lam_diag: jnp.ndarray | None = None
    deadline: float | None = None   # absolute time.perf_counter() stamp


@dataclasses.dataclass(frozen=True)
class GLMSolution:
    req_id: int
    x: jnp.ndarray           # (d,) solution in the request's coordinates
    family: str
    decrement: float         # certificate: final Newton decrement λ̃²/2
    converged: bool          # decrement cleared the service tolerance
    newton_iters: int        # accepted outer Newton steps
    m_trajectory: tuple      # certificate: inner m_final after each step
    m_final: int             # last adapted sketch size
    inner_iters: int         # total inner (PCG/IHS) iterations
    shape_class: ShapeClass
    batch_index: int
    sketch: str = "gaussian"
    # sketch-pass precision that produced this certificate (the δ̃/decrement
    # numbers themselves are always fp32 — DESIGN.md §10)
    compute_dtype: str = "fp32"
    # failure-lattice verdict (DESIGN.md §9); names from SolveStatus
    status: str = "OK"
    stalled: bool = False    # terminated above tolerance (distinct from
                             # "done": frozen line search / outer budget)
    retries: int = 0         # sketch redraws consumed (0 on the GLM path)
    fell_back: bool = False  # answer from the dense fallback, no certificate


@dataclasses.dataclass(frozen=True)
class RidgeSolution:
    req_id: int
    x: jnp.ndarray           # (d,) solution in the request's coordinates
    delta_tilde: float       # certificate: final δ̃ (eq. 2.3)
    m_final: int             # certificate: adapted sketch size
    iters: int               # accepted iterations
    doublings: int
    shape_class: ShapeClass
    batch_index: int         # slot in the packed batch (observability)
    sketch: str = "gaussian"  # sketch family that produced the certificate
    # sketch-pass precision that produced this certificate (the δ̃ value
    # itself is always fp32 — DESIGN.md §10)
    compute_dtype: str = "fp32"
    # failure-lattice verdict (DESIGN.md §9); names from SolveStatus
    status: str = "OK"
    converged: bool = True   # δ̃ cleared the service tolerance
    stalled: bool = False    # terminated above tolerance — previously this
                             # was folded into "done" and indistinguishable
                             # from convergence without re-deriving it from δ̃
    retries: int = 0         # sketch redraws consumed before this answer
    fell_back: bool = False  # answer from direct_solve, no δ̃ certificate
    cache_hit: bool = False  # the λ-free ladder came from the fingerprint
                             # cache — this answer skipped the sketch pass


@dataclasses.dataclass(frozen=True)
class PathPoint:
    """One λ point of a ``PathSolution`` — the same certificate surface a
    single ``RidgeSolution`` carries, per grid point."""
    nu: float
    x: jnp.ndarray           # (d,) solution in the request's coordinates
    delta_tilde: float       # certificate: final δ̃ (eq. 2.3) at this λ
    m_final: int             # certificate: adapted sketch size at this λ
    iters: int
    doublings: int
    status: str = "OK"
    converged: bool = True
    retries: int = 0
    fell_back: bool = False


@dataclasses.dataclass(frozen=True)
class PathSolution:
    req_id: int
    points: tuple            # P PathPoints, in the request's grid order
    shape_class: ShapeClass
    batch_index: int
    sketch: str = "gaussian"
    compute_dtype: str = "fp32"
    status: str = "OK"       # OK iff every point converged, else the first
                             # non-converged point's status
    converged: bool = True   # every point cleared the service tolerance
    cache_hit: bool = False  # the ladder came from the fingerprint cache
    sketch_passes: int = 1   # one-touch passes this request's chunk paid
                             # for the WHOLE grid (0 on a cache hit;
                             # +1 per sketch-redraw retry)


class SolverService:
    """Shape-class bucketing + batch packing over the padded adaptive engine.

    ``submit`` enqueues; ``flush`` drains every bucket in fixed-size batches
    through one compiled executable per shape class and returns solutions
    keyed by request id. The service is deterministic: request k is solved
    with ``fold_in(base_key, k)`` regardless of what it is packed with;
    padded slots draw from the reserved top-of-range id stream
    ``fold_in(base_key, 2³²−1−slot)`` — disjoint from any realistic
    request id — so a padded slot can never alias a real request's sketch
    (previously every padded slot shared the all-zeros key).

    ``compute_dtype`` (service default, overridable per shape class):
    precision of the engine's one-touch sketch pass — "fp32" / "bf16" /
    "int8" (``kernels.precision``). Certificates (δ̃, Newton decrement)
    are fp32 in every mode; each solution records the mode that produced
    it so callers can audit precision alongside convergence.

    ``mesh``: a ``jax.sharding.Mesh`` turns on the sharded mode — each
    packed batch's A is placed row-sharded over the mesh's data axes and
    the engine runs with ``mesh=`` (the sharded one-touch ladder precompute
    + GSPMD loop, DESIGN.md §5). Every shape class's n must divide by the
    data-shard count; the large-n tail classes only fit devices at all
    this way.
    """

    def __init__(
        self,
        shape_classes: Iterable[ShapeClass] | None = None,
        *,
        batch_size: int = 16,
        method: str = "pcg",
        sketch: str = "gaussian",
        compute_dtype: str = "fp32",
        rho: float = 0.5,
        tol: float = 1e-10,
        max_iters: int = 200,
        seed: int = 0,
        mesh=None,
        strict: bool = True,
        max_retries: int = 2,
        fallback: bool = True,
        flush_deadline_s: float | None = None,
        segment_trips: int = 32,
        checkpoint_dir=None,
        preempt=None,
        ladder_cache: bool = False,
        ladder_cache_size: int = 64,
    ):
        if shape_classes is None:
            # the pod-scale n=65536 tail only exists where the batch is
            # actually sharded; a 1-device service must keep failing fast
            shape_classes = (SHARDED_SHAPE_CLASSES if mesh is not None
                             else DEFAULT_SHAPE_CLASSES)
        self.shape_classes = sorted(shape_classes,
                                    key=lambda c: (c.n, c.d, c.m_max))
        self.batch_size = batch_size
        self.method = method
        self.sketch = sketch
        self.compute_dtype = compute_dtype
        self.rho = rho
        self.tol = tol
        self.max_iters = max_iters
        self.mesh = mesh
        if mesh is not None:
            k = n_data_shards(mesh)
            bad = [c for c in self.shape_classes if c.n % k]
            if bad:
                raise ValueError(
                    f"shape classes {bad} have n not divisible by the "
                    f"mesh's {k} data shards")
        self._base_key = jax.random.PRNGKey(seed)
        self._queues: dict[ShapeClass, list[RidgeRequest]] = {
            c: [] for c in self.shape_classes}
        # GLM traffic buckets by (shape class, family): one Newton-driver
        # batch per family so the objective stays a static jit argument
        self._glm_queues: dict[tuple[ShapeClass, str], list[GLMRequest]] = {}
        # path traffic buckets by (shape class, grid length): requests in a
        # packed path chunk must agree on P (the per-problem grids pack to
        # one (P, B) array); the grids themselves may differ per slot
        self._path_queues: dict[tuple[ShapeClass, int],
                                list[PathRequest]] = {}
        # opt-in λ-free-ladder cache (DESIGN.md §13): fingerprint →
        # (per-slot (L, d, d) level-Gram slice, (d, d) true-Gram slice),
        # LRU-bounded. When on, each slot's sketch keys off the FINGERPRINT
        # (content identity) instead of the request id, so identical
        # repeated data reuses the identical sketch — the cache invariant.
        self.ladder_cache = bool(ladder_cache)
        self.ladder_cache_size = int(ladder_cache_size)
        self._ladder_store: OrderedDict[str, tuple] = OrderedDict()
        self._next_id = 0
        self.newton_iters = 30
        self.newton_tol = 1e-9
        # failure-model knobs (DESIGN.md §9): strict=True raises on invalid
        # data at submit; strict=False quarantines the request and returns a
        # REJECTED solution at flush so one bad tenant cannot crash the
        # caller's whole submit loop. max_retries / fallback parameterize
        # core.robust; flush_deadline_s is the default per-flush budget.
        self.strict = strict
        self.max_retries = max_retries
        self.fallback = fallback
        self.flush_deadline_s = flush_deadline_s
        # preemptible-solve knobs (DESIGN.md §11): segment_trips bounds each
        # engine dispatch so deadlines/preemption bind mid-solve;
        # checkpoint_dir persists per-chunk solver state (deterministic
        # directory names, so a restarted process resumes its chunks);
        # preempt is an ft.PreemptionHandler polled between segments.
        self.segment_trips = segment_trips
        self.checkpoint_dir = checkpoint_dir
        self.preempt = preempt
        self._quarantined: dict[int, "RidgeSolution | GLMSolution"] = {}
        self.rejection_reasons: dict[int, str] = {}
        self.stats = {"requests": 0, "batches": 0, "padded_slots": 0,
                      "solve_seconds": 0.0, "retries": 0, "fallbacks": 0,
                      "rejected": 0, "deadline_exceeded": 0,
                      "segments": 0, "resumed_chunks": 0,
                      "path_requests": 0, "ladder_cache_hits": 0,
                      "ladder_cache_misses": 0, "sketch_passes_saved": 0}

    def slot_utilization(self) -> float:
        """Fraction of solved batch slots that held a real request."""
        total = self.stats["batches"] * self.batch_size
        if not total:
            return 1.0
        return 1.0 - self.stats["padded_slots"] / total

    # -- bucketing ---------------------------------------------------------
    def bucket_for(self, n: int, d: int) -> ShapeClass:
        """Smallest configured shape class that fits an (n, d) request."""
        for c in self.shape_classes:
            if n <= c.n and d <= c.d:
                return c
        raise ValueError(
            f"no shape class fits (n={n}, d={d}); "
            f"largest is {self.shape_classes[-1]}")

    def submit(self, A, y, nu, lam_diag=None, *,
               deadline_s: float | None = None) -> int:
        """Enqueue one ridge problem; returns its request id.

        ``deadline_s``: per-request wall-clock budget, counted from submit.
        Urgent requests are dispatched earliest-deadline-first at flush,
        and the deadline binds MID-solve (the segmented engine): a request
        that runs out of time returns its best finite iterate, its real δ̃
        and an honest ``DEADLINE_EXCEEDED``; one whose budget is already
        spent before its chunk dispatches returns x = 0 with no
        certificate.

        ν must be a positive finite float: the service pads requests to the
        class shape with zero A-columns and Λ = 1 on padded coordinates, so
        H restricted to the padded block is ν²·I — with ν = 0 that block is
        singular, its Cholesky is NaN, and the NaN silently poisons the
        problem's solution AND its δ̃/m_final certificates (no exception is
        ever raised inside the jitted engine). The same argument applies to
        NaN/Inf entries in A, y or Λ — submit is the only place the failure
        is observable before it becomes a wrong answer, so admission
        validates all of them: ``strict=True`` raises naming the request,
        ``strict=False`` quarantines it into a ``REJECTED`` solution at
        flush (the engine guards remain the backstop either way).
        """
        A = jnp.asarray(A)
        y = jnp.asarray(y)
        cls = self.bucket_for(*A.shape)     # shape errors always raise
        nu, reason = self._validate(A, y, nu, lam_diag)
        rid = self._next_id
        self._next_id += 1
        self.stats["requests"] += 1
        if reason is not None:
            self._reject(rid, reason, RidgeSolution(
                req_id=rid, x=jnp.zeros((A.shape[1],), A.dtype),
                delta_tilde=float("nan"), m_final=0, iters=0, doublings=0,
                shape_class=cls, batch_index=-1, sketch=cls.sketch or
                self.sketch,
                compute_dtype=cls.compute_dtype or self.compute_dtype,
                status=SolveStatus.REJECTED.name,
                converged=False))
            return rid
        deadline = (None if deadline_s is None
                    else time.perf_counter() + float(deadline_s))
        self._queues[cls].append(RidgeRequest(
            req_id=rid, A=A, y=y, nu=nu, lam_diag=lam_diag,
            deadline=deadline))
        return rid

    def _validate(self, A, y, nu, lam_diag) -> tuple[float, str | None]:
        """Admission checks beyond shape. Returns (ν, reason); reason is
        None iff admissible. In strict mode an inadmissible request raises
        a ValueError naming the request id it would have been assigned."""
        import numpy as np

        reason = None
        try:
            nu = self._check_nu(nu)
        except ValueError as e:
            reason = str(e)
            nu = float("nan")
        if reason is None and y.shape != (A.shape[0],):
            # malformed geometry is a caller bug, not bad data: always raise
            raise ValueError(
                f"y has shape {y.shape}, expected ({A.shape[0]},) to match A")
        if reason is None and not bool(np.all(np.isfinite(np.asarray(A)))):
            reason = "non-finite entries in A"
        if reason is None and not bool(np.all(np.isfinite(np.asarray(y)))):
            reason = "non-finite entries in y"
        if reason is None and lam_diag is not None and not bool(
                np.all(np.isfinite(np.asarray(lam_diag)))):
            reason = "non-finite entries in lam_diag"
        if reason is not None and self.strict:
            raise ValueError(
                f"request {self._next_id} rejected: {reason}")
        return nu, reason

    def _reject(self, rid: int, reason: str, solution) -> None:
        """Quarantine an inadmissible request (strict=False): it never
        touches a packed batch and comes back REJECTED at flush."""
        self._quarantined[rid] = solution
        self.rejection_reasons[rid] = reason
        self.stats["rejected"] += 1

    @staticmethod
    def _check_nu(nu) -> float:
        nu = float(nu)
        if not math.isfinite(nu) or nu <= 0.0:
            raise ValueError(
                f"nu must be a positive finite float, got {nu!r}: padded "
                "coordinates carry H = ν²·I, so ν = 0 makes the padded "
                "block singular and NaN-poisons the certificates")
        return nu

    def submit_glm(self, A, y, nu, family: str = "logistic",
                   lam_diag=None, *, deadline_s: float | None = None) -> int:
        """Enqueue one regularized GLM problem (``family``: logistic /
        poisson / huber[:delta]); returns its request id.

        Padding is the same block-diagonal argument as ridge: padded
        COLUMNS never enter the loss (A-columns are zero) and carry
        ν²Λ = ν²·I, so their optimum is exactly 0 and the solution
        restricted to the request's coordinates is unchanged; padded ROWS
        are all-zero data rows whose loss term ℓ(0, 0) is a constant —
        zero gradient, zero Hessian weight contribution.

        Admission validation mirrors ``submit`` (finiteness of A/y/Λ and
        ν > 0; strict raise vs quarantine), as does ``deadline_s`` (EDF
        dispatch; the budget binds between the Newton driver's outer
        steps)."""
        get_objective(family)          # validate the family name up front
        A = jnp.asarray(A)
        y = jnp.asarray(y)
        cls = self.bucket_for(*A.shape)     # shape errors always raise
        nu, reason = self._validate(A, y, nu, lam_diag)
        rid = self._next_id
        self._next_id += 1
        self.stats["requests"] += 1
        if reason is not None:
            self._reject(rid, reason, GLMSolution(
                req_id=rid, x=jnp.zeros((A.shape[1],), A.dtype),
                family=family, decrement=float("nan"), converged=False,
                newton_iters=0, m_trajectory=(), m_final=0, inner_iters=0,
                shape_class=cls, batch_index=-1,
                sketch=cls.sketch or self.sketch,
                compute_dtype=cls.compute_dtype or self.compute_dtype,
                status=SolveStatus.REJECTED.name))
            return rid
        deadline = (None if deadline_s is None
                    else time.perf_counter() + float(deadline_s))
        req = GLMRequest(req_id=rid, A=A, y=y, nu=nu,
                         family=family, lam_diag=lam_diag, deadline=deadline)
        self._glm_queues.setdefault((cls, family), []).append(req)
        return rid

    def submit_path(self, A, y, nus, lam_diag=None, *,
                    deadline_s: float | None = None) -> int:
        """Enqueue one ridge problem against a λ GRID; returns its request
        id. The flush returns a ``PathSolution`` whose per-λ ``PathPoint``s
        each carry the full δ̃/m/status certificate.

        ``nus`` is the grid of ν values, walked in the given order with x
        and the sketch level warm-started point-to-point — sort it
        strong→weak regularization so warm starts move downhill. The whole
        grid is solved off ONE one-touch sketch pass (the ladder-level
        Grams are λ-free — DESIGN.md §13); requests with equal grid
        lengths pack into one chunk even when their grids differ.

        Admission validates what ``submit`` validates, for EVERY grid
        point's ν (each λ point pads the problem to the class shape, so a
        single ν = 0 in the grid would NaN-poison that point)."""
        import numpy as np

        A = jnp.asarray(A)
        y = jnp.asarray(y)
        cls = self.bucket_for(*A.shape)     # shape errors always raise
        nus = tuple(float(v) for v in np.ravel(np.asarray(nus)))
        if not nus:
            raise ValueError("submit_path needs a non-empty λ grid")
        reason = None
        try:
            for v in nus:
                self._check_nu(v)
        except ValueError as e:
            reason = str(e)
            if self.strict:
                raise ValueError(
                    f"request {self._next_id} rejected: {reason}") from e
        if reason is None:
            _, reason = self._validate(A, y, nus[0], lam_diag)
        rid = self._next_id
        self._next_id += 1
        self.stats["requests"] += 1
        self.stats["path_requests"] += 1
        sketch = cls.sketch or self.sketch
        cd = cls.compute_dtype or self.compute_dtype
        if reason is not None:
            zero = jnp.zeros((A.shape[1],), A.dtype)
            pts = tuple(PathPoint(
                nu=v, x=zero, delta_tilde=float("nan"), m_final=0, iters=0,
                doublings=0, status=SolveStatus.REJECTED.name,
                converged=False) for v in nus)
            self._reject(rid, reason, PathSolution(
                req_id=rid, points=pts, shape_class=cls, batch_index=-1,
                sketch=sketch, compute_dtype=cd,
                status=SolveStatus.REJECTED.name, converged=False,
                sketch_passes=0))
            return rid
        deadline = (None if deadline_s is None
                    else time.perf_counter() + float(deadline_s))
        self._path_queues.setdefault((cls, len(nus)), []).append(PathRequest(
            req_id=rid, A=A, y=y, nus=nus, lam_diag=lam_diag,
            deadline=deadline))
        return rid

    # -- packing -----------------------------------------------------------
    def _pack(self, cls: ShapeClass, reqs: list[RidgeRequest],
              slot_ids: list[int] | None = None):
        """Pad each request to the class shape and stack; pad the batch to
        ``batch_size`` with trivial (b = 0) problems.

        Staged in host numpy buffers (in-place writes) with ONE device
        transfer per field — out-of-jit `.at[i].set` would copy the full
        padded batch buffer once per request. Per-slot keys are one vmapped
        ``fold_in`` over the slot-id vector (real slots: req_id; padded
        slots: the reserved top-of-range id 2³²−1−slot, so padding never
        aliases a real request's sketch) — no per-request host↔device
        round trips.

        ``slot_ids`` overrides the real slots' key ids (the ladder cache
        keys slots by content fingerprint instead of request id, so
        identical data draws the identical sketch)."""
        import numpy as np

        B = self.batch_size
        dtype = np.dtype(reqs[0].A.dtype)
        A = np.zeros((B, cls.n, cls.d), dtype)
        b = np.zeros((B, cls.d), dtype)
        nu = np.ones((B,), dtype)
        lam = np.ones((B, cls.d), dtype)
        for i, r in enumerate(reqs):
            ni, di = r.A.shape
            A[i, :ni, :di] = np.asarray(r.A, dtype)
            b[i, :di] = np.asarray(jnp.matmul(
                r.A.T, r.y, precision=jax.lax.Precision.HIGHEST), dtype)
            nu[i] = r.nu
            if r.lam_diag is not None:
                lam[i, :di] = np.asarray(r.lam_diag, dtype)
        real_ids = ([r.req_id for r in reqs] if slot_ids is None
                    else list(slot_ids))
        slot_ids = jnp.asarray(
            real_ids + [0xFFFFFFFF - s for s in range(len(reqs), B)],
            jnp.uint32)
        keys = jax.vmap(
            lambda i: jax.random.fold_in(self._base_key, i))(slot_ids)
        q = Quadratic(A=jnp.asarray(A), b=jnp.asarray(b), nu=jnp.asarray(nu),
                      lam_diag=jnp.asarray(lam), batched=True)
        if self.mesh is not None:
            q = shard_quadratic(q, self.mesh)
        return q, keys

    def _pack_glm(self, cls: ShapeClass, reqs: list[GLMRequest]):
        """Pad each GLM request to the class shape and stack (A, y, ν, Λ);
        empty slots are all-zero problems (x = 0 is optimal, decrement 0 ⇒
        the Newton driver freezes them at step one). Same staging + key
        scheme as ``_pack``."""
        import numpy as np

        B = self.batch_size
        dtype = np.dtype(reqs[0].A.dtype)
        A = np.zeros((B, cls.n, cls.d), dtype)
        y = np.zeros((B, cls.n), dtype)
        nu = np.ones((B,), dtype)
        lam = np.ones((B, cls.d), dtype)
        for i, r in enumerate(reqs):
            ni, di = r.A.shape
            A[i, :ni, :di] = np.asarray(r.A, dtype)
            y[i, :ni] = np.asarray(r.y, dtype)
            nu[i] = r.nu
            if r.lam_diag is not None:
                lam[i, :di] = np.asarray(r.lam_diag, dtype)
        slot_ids = jnp.asarray(
            [r.req_id for r in reqs]
            + [0xFFFFFFFF - s for s in range(len(reqs), B)], jnp.uint32)
        keys = jax.vmap(
            lambda i: jax.random.fold_in(self._base_key, i))(slot_ids)
        return (jnp.asarray(A), jnp.asarray(y), jnp.asarray(nu),
                jnp.asarray(lam), keys)

    # -- solving -----------------------------------------------------------
    def flush(self, deadline_s: float | None = None
              ) -> "dict[int, RidgeSolution | GLMSolution]":
        """Solve everything queued; returns {req_id: solution} (ridge and
        GLM requests come back in one map, each with its certificate type).

        ``deadline_s`` (default: the service's ``flush_deadline_s``) is a
        per-flush wall-clock budget. Chunks dispatch **earliest-deadline-
        first**: within each queue requests sort by their per-request
        deadline (undeadlined last, insertion order preserved), and across
        queues the chunk with the most urgent member goes first — a
        just-submitted urgent request is no longer stuck behind a backlog
        of patient ones. Each dispatched chunk gets the minimum of the
        remaining flush budget and its most urgent member's remaining
        budget, and the deadline binds MID-solve through the segmented
        engine (``DESIGN.md §11``): requests that run out of time come back
        with their best finite iterate, its real δ̃, and an honest
        ``DEADLINE_EXCEEDED``. A chunk whose budget is already spent
        before dispatch is expired wholesale (x = 0, no certificate).
        Quarantined (REJECTED) requests are always returned first; they
        cost no solve time.

        With ``checkpoint_dir``/``preempt`` set, each chunk solve
        checkpoints between segments and a SIGTERM raises
        ``core.PreemptedError`` out of flush after committing state; a
        restarted service that receives the SAME submissions (ids and
        problems — the deterministic replay contract) resumes each chunk
        from its last committed segment.
        """
        if deadline_s is None:
            deadline_s = self.flush_deadline_s
        t0 = time.perf_counter()
        out: dict[int, RidgeSolution | GLMSolution] = {}
        out.update(self._quarantined)
        self._quarantined = {}

        def edf(queue):
            # stable: deadlined requests first by deadline, rest in
            # insertion order
            return sorted(queue, key=lambda r: (r.deadline is None,
                                                r.deadline or 0.0))

        # (urgency, seq, cls, family|None, chunk) — family=None ⇒ ridge
        chunks = []
        seq = 0
        for cls in self.shape_classes:
            queue, self._queues[cls] = self._queues[cls], []
            queue = edf(queue)
            for i in range(0, len(queue), self.batch_size):
                chunk = queue[i: i + self.batch_size]
                dl = [r.deadline for r in chunk if r.deadline is not None]
                chunks.append((min(dl) if dl else None, seq, cls, None, chunk))
                seq += 1
        for (cls, family), queue in list(self._glm_queues.items()):
            self._glm_queues[(cls, family)] = []
            queue = edf(queue)
            for i in range(0, len(queue), self.batch_size):
                chunk = queue[i: i + self.batch_size]
                dl = [r.deadline for r in chunk if r.deadline is not None]
                chunks.append((min(dl) if dl else None, seq, cls, family,
                               chunk))
                seq += 1
        # path chunks carry kind=("path", P); budgets bind whole-chunk
        # (expire-before-dispatch), not mid-solve
        for (cls, P), queue in list(self._path_queues.items()):
            self._path_queues[(cls, P)] = []
            queue = edf(queue)
            for i in range(0, len(queue), self.batch_size):
                chunk = queue[i: i + self.batch_size]
                dl = [r.deadline for r in chunk if r.deadline is not None]
                chunks.append((min(dl) if dl else None, seq, cls,
                               ("path", P), chunk))
                seq += 1
        chunks.sort(key=lambda c: (c[0] is None, c[0] or 0.0, c[1]))

        for chunk_deadline, _, cls, family, chunk in chunks:
            now = time.perf_counter()
            budgets = []
            if deadline_s is not None:
                budgets.append(deadline_s - (now - t0))
            if chunk_deadline is not None:
                budgets.append(chunk_deadline - now)
            budget = min(budgets) if budgets else None
            if budget is not None and budget <= 0:
                out.update(self._expire_chunk(cls, chunk, family=family))
            elif family is None:
                out.update(self._solve_chunk(cls, chunk, budget_s=budget))
            elif isinstance(family, tuple):
                out.update(self._solve_path_chunk(cls, chunk))
            else:
                out.update(self._solve_glm_chunk(cls, family, chunk,
                                                 budget_s=budget))
        return out

    def _chunk_checkpoint(self, cls: ShapeClass, reqs,
                          family: str | None = None):
        """Per-chunk CheckpointManager under ``checkpoint_dir``, with a
        DETERMINISTIC directory name derived from the chunk's membership —
        a restarted process that replays the same submissions re-derives
        the same directory and resumes the committed state."""
        if self.checkpoint_dir is None:
            return None
        import hashlib
        from pathlib import Path

        from repro.ft.checkpoint import CheckpointManager

        ids = ",".join(str(r.req_id) for r in reqs)
        token = f"{cls.n}x{cls.d}x{cls.m_max}:{family or 'ridge'}:{ids}"
        tag = hashlib.sha1(token.encode()).hexdigest()[:12]
        return CheckpointManager(Path(self.checkpoint_dir) / f"chunk_{tag}")

    def _expire_chunk(self, cls: ShapeClass, reqs, family: str | None = None):
        """DEADLINE_EXCEEDED solutions for an undispatched chunk."""
        out = {}
        name = SolveStatus.DEADLINE_EXCEEDED.name
        sketch = cls.sketch or self.sketch
        cd = cls.compute_dtype or self.compute_dtype
        for r in reqs:
            zero = jnp.zeros((r.A.shape[1],), r.A.dtype)
            if family is None:
                out[r.req_id] = RidgeSolution(
                    req_id=r.req_id, x=zero, delta_tilde=float("nan"),
                    m_final=0, iters=0, doublings=0, shape_class=cls,
                    batch_index=-1, sketch=sketch, compute_dtype=cd,
                    status=name, converged=False)
            elif isinstance(family, tuple):
                pts = tuple(PathPoint(
                    nu=v, x=zero, delta_tilde=float("nan"), m_final=0,
                    iters=0, doublings=0, status=name, converged=False)
                    for v in r.nus)
                out[r.req_id] = PathSolution(
                    req_id=r.req_id, points=pts, shape_class=cls,
                    batch_index=-1, sketch=sketch, compute_dtype=cd,
                    status=name, converged=False, sketch_passes=0)
            else:
                out[r.req_id] = GLMSolution(
                    req_id=r.req_id, x=zero, family=family,
                    decrement=float("nan"), converged=False, newton_iters=0,
                    m_trajectory=(), m_final=0, inner_iters=0,
                    shape_class=cls, batch_index=-1, sketch=sketch,
                    compute_dtype=cd, status=name)
            self.stats["deadline_exceeded"] += 1
        return out

    # -- λ-free ladder cache (DESIGN.md §13) -------------------------------
    def _ladder_fingerprint(self, A, lam_diag, cls: ShapeClass,
                            sketch: str, cd: str) -> str:
        """Content identity of a slot's λ-free ladder: the data, the
        regularizer GEOMETRY (Λ — not ν: the level Grams are λ-free), the
        class shape/budget, the sketch family and the sketch-pass
        precision. Everything that determines the (L, d, d) gram slice
        given the fingerprint-derived slot key."""
        import hashlib

        import numpy as np

        h = hashlib.sha1()
        h.update(f"{cls.n}x{cls.d}x{cls.m_max}:{sketch}:{cd}:".encode())
        h.update(np.ascontiguousarray(np.asarray(A)).tobytes())
        h.update(b"|lam:")
        if lam_diag is not None:
            h.update(np.ascontiguousarray(np.asarray(lam_diag)).tobytes())
        return h.hexdigest()

    @staticmethod
    def _fp_slot_id(fp: str) -> int:
        """Sketch-key id for a fingerprinted slot (the cache invariant:
        identical content ⇒ identical sketch). Bit 31 is cleared so the
        id stream stays disjoint from the padded slots' reserved
        top-of-range ids."""
        return int(fp[:8], 16) & 0x7FFFFFFF

    def _ladder_assets(self, cls: ShapeClass, fps: list[str], q, keys,
                       sketch: str, cd: str):
        """Serve a chunk's λ-free ladder through the fingerprint cache.

        All real slots cached ⇒ assemble the (L, B, d, d) ladder and the
        (B, d, d) true Gram from the stored per-slot slices — the chunk
        SKIPS its sketch pass entirely (padded slots have A = 0 ⇒ zero
        Grams). Any miss ⇒ run the one-touch pass ONCE for the whole
        chunk (``prepare_path_ladder``) and cache the new slices.
        Returns ``(grams, gram_full, skipped)``."""
        import numpy as np

        B = self.batch_size
        L = len(doubling_ladder(cls.m_max))
        hits = [fp in self._ladder_store for fp in fps]
        if all(hits):
            dt = np.dtype(np.asarray(q.b).dtype)
            grams = np.zeros((L, B, cls.d, cls.d), dt)
            gfull = np.zeros((B, cls.d, cls.d), dt)
            for i, fp in enumerate(fps):
                g, f = self._ladder_store[fp]
                self._ladder_store.move_to_end(fp)
                grams[:, i] = g
                gfull[i] = f
            self.stats["ladder_cache_hits"] += len(fps)
            self.stats["sketch_passes_saved"] += 1
            return jnp.asarray(grams), jnp.asarray(gfull), True
        grams, gfull = prepare_path_ladder(
            q, keys, m_max=cls.m_max, sketch=sketch, gram_hvp=True,
            mesh=self.mesh, compute_dtype=cd)
        gn, fn = np.asarray(grams), np.asarray(gfull)
        for i, (fp, hit) in enumerate(zip(fps, hits)):
            if hit:
                self.stats["ladder_cache_hits"] += 1
                self._ladder_store.move_to_end(fp)
            else:
                self.stats["ladder_cache_misses"] += 1
                self._ladder_store[fp] = (gn[:, i].copy(), fn[i].copy())
        while len(self._ladder_store) > self.ladder_cache_size:
            self._ladder_store.popitem(last=False)
        return grams, gfull, False

    def _solve_path_chunk(self, cls: ShapeClass, reqs: list[PathRequest]):
        """One packed λ-grid chunk: ONE shared λ-free ladder (from the
        cache or one one-touch pass) + per-point warm-started robust
        solves (``core.robust.robust_path_solve_batched``)."""
        import numpy as np

        P = len(reqs[0].nus)
        sketch = cls.sketch or self.sketch
        cd = cls.compute_dtype or self.compute_dtype
        # ride the ridge packer: the packed ν is a placeholder (the path
        # engine reads the (P, B) grid, never q.nu)
        proxies = [RidgeRequest(req_id=r.req_id, A=r.A, y=r.y, nu=1.0,
                                lam_diag=r.lam_diag, deadline=r.deadline)
                   for r in reqs]
        fps = None
        if self.ladder_cache:
            fps = [self._ladder_fingerprint(r.A, r.lam_diag, cls, sketch, cd)
                   for r in reqs]
            q, keys = self._pack(cls, proxies,
                                 slot_ids=[self._fp_slot_id(f) for f in fps])
        else:
            q, keys = self._pack(cls, proxies)
        nus = np.ones((P, self.batch_size),
                      np.dtype(np.asarray(q.b).dtype))
        for i, r in enumerate(reqs):
            nus[:, i] = r.nus
        grams = gfull = None
        skipped = False
        if self.ladder_cache:
            grams, gfull, skipped = self._ladder_assets(
                cls, fps, q, keys, sketch, cd)
        t0 = time.perf_counter()
        xs, stats = robust_path_solve_batched(
            q, keys, jnp.asarray(nus), m_max=cls.m_max, method=self.method,
            sketch=sketch, max_iters=self.max_iters, rho=self.rho,
            tol=self.tol, mesh=self.mesh, max_retries=self.max_retries,
            fallback=self.fallback, compute_dtype=cd,
            grams=grams, gram_full=gfull)
        xs = jax.block_until_ready(xs)
        self.stats["solve_seconds"] += time.perf_counter() - t0
        self.stats["batches"] += 1
        self.stats["padded_slots"] += self.batch_size - len(reqs)
        passes = int(stats["sketch_passes"]) - (1 if skipped else 0)
        out = {}
        for i, r in enumerate(reqs):
            di = r.A.shape[1]
            pts = []
            for p in range(P):
                self.stats["retries"] += int(stats["retries"][p, i])
                self.stats["fallbacks"] += int(stats["fell_back"][p, i])
                pts.append(PathPoint(
                    nu=r.nus[p],
                    x=xs[p, i, :di],
                    delta_tilde=float(stats["dtilde"][p, i]),
                    m_final=int(stats["m_final"][p, i]),
                    iters=int(stats["iters"][p, i]),
                    doublings=int(stats["doublings"][p, i]),
                    status=status_name(stats["status"][p, i]),
                    converged=bool(stats["converged"][p, i]),
                    retries=int(stats["retries"][p, i]),
                    fell_back=bool(stats["fell_back"][p, i]),
                ))
            bad = [pt for pt in pts if not pt.converged]
            out[r.req_id] = PathSolution(
                req_id=r.req_id, points=tuple(pts), shape_class=cls,
                batch_index=i, sketch=sketch, compute_dtype=cd,
                status=bad[0].status if bad else "OK",
                converged=not bad, cache_hit=skipped,
                sketch_passes=passes)
        return out

    def _solve_glm_chunk(self, cls: ShapeClass, family: str,
                         reqs: list[GLMRequest],
                         budget_s: float | None = None):
        A, y, nu, lam, keys = self._pack_glm(cls, reqs)
        sketch = cls.sketch or self.sketch
        cd = cls.compute_dtype or self.compute_dtype
        t0 = time.perf_counter()
        x, stats = adaptive_newton_solve_batched(
            family, A, y, nu, lam_diag=lam, keys=keys, m_max=cls.m_max,
            method=self.method, sketch=sketch,
            newton_iters=self.newton_iters, tol=self.newton_tol,
            inner_max_iters=self.max_iters, rho=self.rho,
            inner_tol=self.tol, mesh=self.mesh, compute_dtype=cd,
            deadline_s=budget_s)
        x = jax.block_until_ready(x)
        self.stats["solve_seconds"] += time.perf_counter() - t0
        self.stats["batches"] += 1
        self.stats["padded_slots"] += self.batch_size - len(reqs)
        out = {}
        m_traj = stats["m_trajectory"]                       # (T, B)
        for i, r in enumerate(reqs):
            di = r.A.shape[1]
            traj = tuple(int(m) for m in m_traj[:, i] if m > 0)
            if int(stats["status"][i]) == int(SolveStatus.DEADLINE_EXCEEDED):
                self.stats["deadline_exceeded"] += 1
            out[r.req_id] = GLMSolution(
                req_id=r.req_id,
                x=x[i, :di],
                family=family,
                decrement=float(stats["decrement"][i]),
                converged=bool(stats["converged"][i]),
                newton_iters=int(stats["newton_iters"][i]),
                m_trajectory=traj,
                m_final=int(stats["m_final"][i]),
                inner_iters=int(stats["inner_iters"][i]),
                shape_class=cls,
                batch_index=i,
                sketch=sketch,
                compute_dtype=cd,
                status=status_name(stats["status"][i]),
                stalled=bool(stats["stalled"][i]),
            )
        return out

    def _solve_chunk(self, cls: ShapeClass, reqs: list[RidgeRequest],
                     budget_s: float | None = None):
        sketch = cls.sketch or self.sketch
        cd = cls.compute_dtype or self.compute_dtype
        grams = gfull = None
        skipped = False
        if self.ladder_cache:
            fps = [self._ladder_fingerprint(r.A, r.lam_diag, cls, sketch, cd)
                   for r in reqs]
            q, keys = self._pack(cls, reqs,
                                 slot_ids=[self._fp_slot_id(f) for f in fps])
            grams, gfull, skipped = self._ladder_assets(
                cls, fps, q, keys, sketch, cd)
        else:
            q, keys = self._pack(cls, reqs)
        t0 = time.perf_counter()
        # the robust driver = guarded engine + per-problem sketch-redraw
        # retries + direct_solve degradation; a quarantine-evading fault
        # (e.g. numerically degenerate but finite data) still ends in a
        # finite answer with an honest verdict, isolated to its slot.
        # Any preemptibility knob (budget / checkpoint / SIGTERM handler)
        # routes the solve through the segmented driver; with none set the
        # call — and its numbers — are the single-dispatch ones.
        seg_kwargs = {}
        if (budget_s is not None or self.checkpoint_dir is not None
                or self.preempt is not None):
            seg_kwargs = dict(
                deadline_s=budget_s,
                segment_trips=self.segment_trips,
                checkpoint=self._chunk_checkpoint(cls, reqs),
                preempt=self.preempt,
            )
        x, stats = robust_padded_solve_batched(
            q, keys, m_max=cls.m_max, method=self.method, sketch=sketch,
            max_iters=self.max_iters, rho=self.rho, tol=self.tol,
            mesh=self.mesh, max_retries=self.max_retries,
            fallback=self.fallback, compute_dtype=cd,
            grams=grams, gram_full=gfull, **seg_kwargs)
        x = jax.block_until_ready(x)
        self.stats["solve_seconds"] += time.perf_counter() - t0
        self.stats["batches"] += 1
        self.stats["padded_slots"] += self.batch_size - len(reqs)
        self.stats["segments"] += int(stats.get("segments", 0))
        self.stats["resumed_chunks"] += int(bool(stats.get("resumed", False)))
        out = {}
        for i, r in enumerate(reqs):
            di = r.A.shape[1]
            self.stats["retries"] += int(stats["retries"][i])
            self.stats["fallbacks"] += int(stats["fell_back"][i])
            if int(stats["status"][i]) == int(SolveStatus.DEADLINE_EXCEEDED):
                self.stats["deadline_exceeded"] += 1
            out[r.req_id] = RidgeSolution(
                req_id=r.req_id,
                x=x[i, :di],
                delta_tilde=float(stats["dtilde"][i]),
                m_final=int(stats["m_final"][i]),
                iters=int(stats["iters"][i]),
                doublings=int(stats["doublings"][i]),
                shape_class=cls,
                batch_index=i,
                sketch=sketch,
                compute_dtype=cd,
                status=status_name(stats["status"][i]),
                converged=bool(stats["converged"][i]),
                stalled=bool(stats["stalled"][i]),
                retries=int(stats["retries"][i]),
                fell_back=bool(stats["fell_back"][i]),
                cache_hit=skipped,
            )
        return out

    def solve_one(self, A, y, nu, lam_diag=None) -> RidgeSolution:
        """Convenience: submit + flush a single request (still batched —
        the padded slots ride along as no-op problems)."""
        rid = self.submit(A, y, nu, lam_diag)
        return self.flush()[rid]
