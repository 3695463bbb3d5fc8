"""Phase scopes and host spans of the padded engine (DESIGN.md §14).

Every solve piece traces under one ``engine.*`` named scope, which XLA
keeps in each op's ``op_name`` metadata; the library entry
``padded_adaptive_solve`` writes three ``repro.solve.*`` profiler spans
around its host work. Checked here on the CPU at a tiny size: the scopes
in the compiled HLO text of every entry point, and the spans in a
profiler trace read with ``jax.profiler.ProfileData``.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import padded_adaptive_solve
from repro.core.adaptive_padded import (
    doubling_ladder, finalize_padded_solve, padded_adaptive_solve_batched,
    padded_solve_segment, prepare_padded_solve, prepare_path_ladder,
    reprecondition_padded)
from repro.core.quadratic import Quadratic

N, D = 256, 32
# m_max < d takes the dual ladder form, m_max = 2d (a service shape class)
# the primal one (DESIGN.md §6)
M_DUAL, M_PRIMAL = 16, 64
M_MAX = M_DUAL
# the instruction kinds that do a phase's work on a device
WORK_OPS = ("fusion", "dot", "convolution", "custom-call", "while")
# work instructions the CPU compiler makes itself, with no metadata: a
# batch-of-one dot_general rewritten as a plain dot, the fusion around such
# a dot, and a broadcast it wraps (at B = 2 only the broadcast is left)
COMPILER_MADE = {"dot", "bitcast_dot_fusion", "wrapped_broadcast"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_NUMBER = re.compile(r"\.\d+$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(engine\.\w+)")


def _problem(batched: bool):
    kA, ky = jax.random.split(jax.random.key(0))
    A = jax.random.normal(kA, (N, D)) * (0.9 ** jnp.arange(D)) / N ** 0.5
    b = A.T @ jax.random.normal(ky, (N,))
    if not batched:
        return Quadratic(A=A, b=b, nu=jnp.float32(0.1),
                         lam_diag=jnp.ones((D,)))
    return Quadratic(A=A, b=b[None], nu=jnp.full((1,), 0.1),
                     lam_diag=jnp.ones((1, D)), batched=True)


def _keys():
    return jax.random.split(jax.random.key(1), 1)


def work_scopes(hlo: str) -> tuple[dict[str, list[tuple[str, ...]]],
                                   set[str]]:
    """For each work instruction of a compiled HLO text that carries an
    ``op_name``, the ``engine.*`` components of that name, by op kind; and
    the names, without their numbers, of those that carry none."""
    out: dict[str, list[tuple[str, ...]]] = {}
    unnamed = set()
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) not in WORK_OPS:
            continue
        name = _OP_NAME.search(line)
        if name:
            out.setdefault(m.group(2), []).append(
                tuple(_SCOPE.findall(name.group(1))))
        else:
            unnamed.add(_NUMBER.sub("", m.group(1)))
    return out, unnamed


def _scopes_of(hlo: str) -> set[str]:
    by_kind, unnamed = work_scopes(hlo)
    assert unnamed <= COMPILER_MADE, unnamed
    assert all(len(s) == 1 for scopes in by_kind.values() for s in scopes)
    return set(_SCOPE.findall(" ".join(_OP_NAME.findall(hlo))))


def _assert_one_phase_scope_per_work_op(m_max, method, gram_hvp):
    hlo = padded_adaptive_solve_batched.lower(
        _problem(True), _keys(), m_max=m_max, method=method,
        gram_hvp=gram_hvp).compile().as_text()
    by_kind, unnamed = work_scopes(hlo)
    assert {"fusion", "dot", "custom-call", "while"} <= set(by_kind)
    # an op without a name is one the compiler made, never one of ours
    assert unnamed <= COMPILER_MADE, unnamed
    seen = set()
    for kind, scopes in by_kind.items():
        for s in scopes:
            assert len(s) == 1, (kind, s)
            seen.add(s[0])
    phases = {"engine.sketch", "engine.factor", "engine.init", "engine.loop",
              "engine.finalize"}
    assert seen == phases | ({"engine.gram"} if gram_hvp else set())
    # the loop's own while op, apart from the sketch pass's chunk scan
    assert ("engine.loop",) in by_kind["while"]


@pytest.mark.parametrize("gram_hvp", [True, False])
@pytest.mark.parametrize("method", ["ihs", "pcg"])
def test_every_work_op_carries_one_phase_scope(method, gram_hvp):
    _assert_one_phase_scope_per_work_op(M_DUAL, method, gram_hvp)


@pytest.mark.parametrize("gram_hvp", [True, False])
@pytest.mark.parametrize("method", ["ihs", "pcg"])
def test_every_work_op_carries_one_phase_scope_in_primal_form(method,
                                                              gram_hvp):
    _assert_one_phase_scope_per_work_op(M_PRIMAL, method, gram_hvp)


def test_dual_form_ops_are_scoped_and_no_d_by_d_matrix_is_formed():
    """In the dual form the row stream is the sketch pass's, K, the level
    Choleskys and the U_l are the factorization's, and no instruction
    makes a d×d array (the matrix-free hvp forms no Gram either)."""
    hlo = padded_adaptive_solve_batched.lower(
        _problem(True), _keys(), m_max=M_DUAL, method="pcg",
        gram_hvp=False).compile().as_text()
    assert f"{D},{D}]" not in hlo
    factor = [line for line in hlo.splitlines()
              if "/engine.factor/" in line]
    assert any("cholesky" in line for line in factor)
    assert any("triangular_solve" in line for line in factor)
    # K = V Vᵀ: a (1, m_max, m_max) contraction, and the (L, 1, m_max, d)
    # table of the U_l
    assert any(f"[1,{M_DUAL},{M_DUAL}]" in line and "dot_general" in line
               for line in factor)
    L = len(doubling_ladder(M_DUAL))
    assert any(f"[{L},1,{M_DUAL},{D}]" in line for line in factor)
    sketch = [line for line in hlo.splitlines() if "/engine.sketch/" in line]
    assert any(f"[1,{M_DUAL},{D}]" in line for line in sketch)


def test_segment_pieces_carry_their_scopes():
    q, keys = _problem(True), _keys()
    kw = dict(m_max=M_MAX, gram_hvp=True)
    hlo = prepare_padded_solve.lower(q, keys, **kw).compile().as_text()
    assert _scopes_of(hlo) == {"engine.sketch", "engine.factor",
                               "engine.gram", "engine.init"}
    pre, st = prepare_padded_solve(q, keys, **kw)
    hlo = padded_solve_segment.lower(q, pre, st, jnp.int32(4),
                                     method="pcg").compile().as_text()
    assert _scopes_of(hlo) == {"engine.loop"}
    hlo = finalize_padded_solve.lower(pre, st,
                                      m_max=M_MAX).compile().as_text()
    assert _scopes_of(hlo) == {"engine.finalize"}
    # the path engine's shared ladder and the mid-solve refactorization
    hlo = prepare_path_ladder.lower(q, keys, **kw).compile().as_text()
    assert _scopes_of(hlo) == {"engine.sketch", "engine.gram"}
    grams, _ = prepare_path_ladder(q, keys, **kw)
    hlo = reprecondition_padded.lower(q, pre, st,
                                      grams).compile().as_text()
    assert _scopes_of(hlo) == {"engine.factor", "engine.init"}


def test_library_entry_writes_its_host_spans_in_order(tmp_path):
    q, key = _problem(False), jax.random.key(2)
    kw = dict(m_max=M_MAX, method="pcg")
    jax.block_until_ready(padded_adaptive_solve(q, key, **kw))   # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        x, stats = padded_adaptive_solve(q, key, **kw)
        jax.block_until_ready((x, stats))
    finally:
        jax.profiler.stop_trace()
    assert int(stats["status"]) == 0
    from jax.profiler import ProfileData

    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    spans = [(e.start_ns, e.end_ns, e.name)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    spans.sort()
    assert [s[2] for s in spans] == ["repro.solve.prepare",
                                     "repro.solve.dispatch",
                                     "repro.solve.unpack"]
    for (_, end, _), (start, _, _) in zip(spans, spans[1:]):
        assert end <= start
