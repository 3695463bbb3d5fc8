"""Exact FLOP counting from optimized (partitioned) HLO text.

``compiled.cost_analysis()`` proved unreliable for large SPMD programs
(loop bodies counted once; at 2²¹×8192 scale the reported flops diverged
~500× from the dot instructions actually present in the module). This
module counts flops from first principles: every ``dot`` instruction in
the partitioned module contributes 2 · prod(output_dims) · prod(contracted
lhs dims). Shapes in the partitioned module are per-device, so the result
is per-device flops — the quantity the roofline compute term needs.

HLO operands are referenced by NAME (``dot(%a.1, %b.1)``), so parsing is
two-pass: build a name → shape table from every instruction definition,
then resolve each dot's lhs shape and contracting dims.

Limitations (documented in EXPERIMENTS.md): while-loop bodies are counted
once (the solver probe unrolls its PCG scan in the analysis sweep, so all
iterations are present); elementwise flops are ignored (≤ a few % for
these workloads); cholesky/triangular-solve flops are added analytically
by the caller when relevant (``roofline.solver_model_flops``).

``dot_flops_for_entry`` connects this counter to the audited solver
surface: any entry point from ``repro.analysis.audit.entrypoints`` can be
compiled for the host platform and measured without executing.
"""

from __future__ import annotations

import re
from collections import Counter

_DTYPES = r"(?:pred|s8|u8|s16|u16|f16|bf16|s32|u32|f32|s64|u64|f64|f8\w*)"
_DEF_RE = re.compile(rf"%([\w.\-]+) = {_DTYPES}\[([0-9,]*)\]")
_DOT_LINE_RE = re.compile(
    rf"%[\w.\-]+ = {_DTYPES}\[([0-9,]*)\][^\n]*?\bdot\(([^)]*)\)"
)
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_BATCH_RE = re.compile(r"lhs_batch_dims=\{([0-9,]*)\}")
# operands carry inline shapes in newer HLO text: dot(f32[3,128,256]{...} %a, …)
_OPERAND_RE = re.compile(rf"(?:{_DTYPES}\[([0-9,]*)\]\S*\s+)?%([\w.\-]+)")


def _prod(dims_csv: str) -> int:
    out = 1
    for t in dims_csv.split(","):
        if t:
            out *= int(t)
    return out


def _name_shapes(hlo_text: str) -> dict[str, list[int]]:
    table: dict[str, list[int]] = {}
    for m in _DEF_RE.finditer(hlo_text):
        dims = [int(t) for t in m.group(2).split(",") if t]
        table[m.group(1)] = dims
    return table


def iter_dots(hlo_text: str):
    """Yields (out_dims_csv, flops) per dot instruction (per device)."""
    shapes = _name_shapes(hlo_text)
    for line in hlo_text.splitlines():
        if "dot(" not in line:
            continue
        m = _DOT_LINE_RE.search(line)
        if not m:
            continue
        out_csv, operands = m.group(1), m.group(2)
        mc = _CONTRACT_RE.search(line)
        if not mc:
            continue
        mo = _OPERAND_RE.search(operands)
        if mo is None:
            continue
        if mo.group(1) is not None:         # inline-shaped operand
            lhs_dims = [int(t) for t in mo.group(1).split(",") if t]
        else:                               # name-referenced operand
            lhs_dims = shapes.get(mo.group(2))
        if lhs_dims is None:
            continue
        contracted = 1
        for i in (int(t) for t in mc.group(1).split(",") if t):
            if i < len(lhs_dims):
                contracted *= lhs_dims[i]
        yield out_csv, 2.0 * _prod(out_csv) * contracted


def dot_flops_from_hlo(hlo_text: str) -> float:
    """Sum of 2·|out|·|contracted| over all dots (per device)."""
    return sum(fl for _, fl in iter_dots(hlo_text))


def dot_flops_for_entry(entry_name: str) -> float:
    """Per-device dot FLOPs of one audited solver entry point (exact name
    from ``repro.analysis.audit.entrypoints.build_targets``), compiled for
    the host platform — lowered and counted, never executed."""
    import jax
    import jax.extend.core as jex_core

    from .audit.entrypoints import build_targets

    for ep in build_targets(quick=False):
        if ep.name == entry_name:
            closed = ep.build()
            fn = jex_core.jaxpr_as_fun(closed)
            args = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                    for a in closed.in_avals]
            hlo = jax.jit(fn).lower(*args).compile().as_text()
            return dot_flops_from_hlo(hlo)
    raise KeyError(f"unknown audit entry point: {entry_name}")


def dot_inventory(hlo_text: str, top: int = 12):
    """[(out_shape, count, flops_each)] sorted by total flops — triage."""
    inv: Counter = Counter()
    fl_each: dict[str, float] = {}
    for out_csv, fl in iter_dots(hlo_text):
        inv[out_csv] += 1
        fl_each[out_csv] = fl
    rows = sorted(
        ((k, c, fl_each[k]) for k, c in inv.items()),
        key=lambda t: -t[1] * t[2],
    )
    return rows[:top]
