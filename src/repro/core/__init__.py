"""Core library: the paper's adaptive sketching-based solvers.

Layout:
  sketches.py       Gaussian / SRHT / SJLT embeddings (+ FWHT reference)
  precond.py        H_S factorizations (Cholesky primal / Woodbury dual)
  quadratic.py      problem container (matrix-free H·v, ∇f)
  solvers.py        IHS / PCG / Polyak-IHS / plain CG
  adaptive.py       Algorithm 4.1 / 4.2 (host-orchestrated doubling)
  adaptive_padded.py  beyond-paper single-XLA-program masked adaptivity,
                    batch-polymorphic multi-problem engine (DESIGN.md §6)
  effective_dim.py  d_e and critical sketch sizes (Table 1 / Thm 5.1)
  distributed.py    row-sharded A: block sketches + GSPMD solver steps
  objectives.py     regularized GLM losses (logistic/poisson/huber/quadratic)
  newton.py         adaptive sketched-Newton driver over the padded engine
  status.py         per-problem SolveStatus failure lattice (DESIGN.md §9)
  robust.py         retry-with-redrawn-sketch + direct-solve fallback driver,
                    segmented/preemptible solve driver (DESIGN.md §11)

Every core op accepts an optional leading problem axis (batched
``Quadratic``) — see quadratic.py and DESIGN.md §6. Weighted Grams AᵀWA
(GLM Newton systems) ride through ``Quadratic.row_weights`` — DESIGN.md §8.
"""

from .adaptive import AdaptiveConfig, AdaptiveResult, adaptive_solve, k_max
from .adaptive_padded import (
    PaddedPrecompute,
    PaddedState,
    finalize_padded_solve,
    padded_adaptive_solve,
    padded_adaptive_solve_batched,
    padded_path_solve_batched,
    padded_solve_segment,
    padded_trip_cap,
    prepare_padded_solve,
    prepare_path_ladder,
    reprecondition_padded,
)
from .effective_dim import (
    effective_dimension,
    effective_dimension_exact,
    effective_dimension_weighted_exact,
    exp_decay_singular_values,
    m_delta_gaussian,
    m_delta_sjlt,
    m_delta_srht,
)
from .newton import (
    adaptive_newton_solve,
    adaptive_newton_solve_batched,
    irls_reference,
    newton_cg_reference,
)
from .objectives import GLM_FAMILIES, GLMObjective, get_objective
from .precond import (
    SketchedPrecond,
    factorize,
    factorize_shared,
    shifted_ladder_inverses,
)
from .quadratic import (
    Quadratic,
    direct_solve,
    from_least_squares,
    from_least_squares_batch,
    lambda_sweep,
    stack_quadratics,
    gram,
)
from .robust import (
    PreemptedError,
    robust_padded_solve_batched,
    robust_path_solve_batched,
    segmented_padded_solve_batched,
)
from .sketches import Sketch, fwht, make_sketch
from .solvers import cg_solve, newton_solve, run_fixed
from .status import (
    CONVERGED_STATUSES,
    ENGINE_FAILURES,
    SolveStatus,
    status_name,
)

__all__ = [
    "AdaptiveConfig",
    "AdaptiveResult",
    "adaptive_solve",
    "padded_adaptive_solve",
    "padded_adaptive_solve_batched",
    "PaddedState",
    "PaddedPrecompute",
    "prepare_padded_solve",
    "prepare_path_ladder",
    "padded_path_solve_batched",
    "padded_solve_segment",
    "finalize_padded_solve",
    "reprecondition_padded",
    "padded_trip_cap",
    "k_max",
    "effective_dimension",
    "effective_dimension_exact",
    "effective_dimension_weighted_exact",
    "exp_decay_singular_values",
    "m_delta_gaussian",
    "m_delta_sjlt",
    "m_delta_srht",
    "SketchedPrecond",
    "factorize",
    "factorize_shared",
    "shifted_ladder_inverses",
    "Quadratic",
    "direct_solve",
    "from_least_squares",
    "from_least_squares_batch",
    "lambda_sweep",
    "stack_quadratics",
    "gram",
    "GLM_FAMILIES",
    "GLMObjective",
    "get_objective",
    "adaptive_newton_solve",
    "adaptive_newton_solve_batched",
    "irls_reference",
    "newton_cg_reference",
    "Sketch",
    "fwht",
    "make_sketch",
    "cg_solve",
    "newton_solve",
    "run_fixed",
    "robust_padded_solve_batched",
    "robust_path_solve_batched",
    "segmented_padded_solve_batched",
    "PreemptedError",
    "SolveStatus",
    "ENGINE_FAILURES",
    "CONVERGED_STATUSES",
    "status_name",
]
