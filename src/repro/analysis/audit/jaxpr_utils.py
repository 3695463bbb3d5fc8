"""The one jaxpr walker every invariant check shares.

Everything here is pure introspection on a ``ClosedJaxpr``: recursion into
sub-jaxprs (scan/while/cond/pjit/shard_map bodies), primitive inventory
with the *context path* each equation sits under (so a rule can ask "is
this psum inside a while_loop body?"), intermediate-aval enumeration for
the memory claims, and source provenance for actionable violation
messages. ``repro.analysis.memscan`` and the tier-1 jaxpr-scan tests are
thin delegations onto this module — the scans used to be copy-pasted per
test file, which meant a new entry point shipped unaudited by default.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Iterator

import jax
import jax.extend.core as jex_core
from jax._src import source_info_util
import numpy as np


def subjaxprs(eqn) -> Iterable:
    """Every sub-jaxpr referenced by an equation's params (scan/while/cond
    bodies, pjit calls, shard_map, custom_* wrappers)."""
    for v in eqn.params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, jex_core.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, jex_core.Jaxpr):
                    yield item


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """An equation plus where it sits: ``path`` is the tuple of enclosing
    primitive names from the root, e.g. ``("pjit", "while")`` for an
    equation inside the engine loop body."""

    eqn: object
    path: tuple[str, ...]

    @property
    def in_while_body(self) -> bool:
        return "while" in self.path

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name


def iter_eqns(closed_jaxpr) -> Iterator[EqnSite]:
    """Yield every equation (recursively) with its enclosing-primitive
    path. Duplicate sub-jaxpr objects are visited once."""
    seen: set[int] = set()

    def walk(jx, path):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            yield EqnSite(eqn, path)
            sub_path = path + (eqn.primitive.name,)
            for sub in subjaxprs(eqn):
                yield from walk(sub, sub_path)

    yield from walk(closed_jaxpr.jaxpr, ())


def collect_eqns(closed_jaxpr, primitive: str | tuple[str, ...]) -> list:
    """All equations (recursively) whose primitive name matches. The
    canonical replacement for the per-test ``psum_eqns`` walkers."""
    names = (primitive,) if isinstance(primitive, str) else tuple(primitive)
    return [s.eqn for s in iter_eqns(closed_jaxpr) if s.primitive in names]


def collect_sites(closed_jaxpr,
                  primitive: str | tuple[str, ...]) -> list[EqnSite]:
    """Like :func:`collect_eqns` but keeps the context path."""
    names = (primitive,) if isinstance(primitive, str) else tuple(primitive)
    return [s for s in iter_eqns(closed_jaxpr) if s.primitive in names]


def count_primitive(closed_jaxpr, primitive: str | tuple[str, ...]) -> int:
    """Recursive occurrence count of a primitive (e.g. one ``scatter-add``
    per SJLT dispatch — the one-touch cap-level claim)."""
    return len(collect_eqns(closed_jaxpr, primitive))


def while_body_jaxprs(closed_jaxpr) -> list:
    """The body jaxprs of every while_loop in the program (the engine's
    adaptive loop; collectives are forbidden inside)."""
    out = []
    for site in iter_eqns(closed_jaxpr):
        if site.primitive == "while":
            body = site.eqn.params.get("body_jaxpr")
            if body is not None:
                out.append(body)
    return out


def iter_intermediate_avals(closed_jaxpr) -> Iterable:
    """Yield the aval of every equation output, recursively."""
    for site in iter_eqns(closed_jaxpr):
        for var in site.eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                yield aval


def aval_bytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize


def max_intermediate_bytes(closed_jaxpr) -> tuple[int, tuple[int, ...]]:
    """(bytes, shape) of the single largest intermediate array produced
    anywhere in the program (sub-jaxprs included)."""
    best, best_shape = 0, ()
    for aval in iter_intermediate_avals(closed_jaxpr):
        nbytes = aval_bytes(aval)
        if nbytes > best:
            best, best_shape = nbytes, tuple(aval.shape)
    return best, best_shape


def has_intermediate_of_shape(closed_jaxpr, shape: tuple[int, ...],
                              dtype=None) -> bool:
    """True if any intermediate anywhere has exactly this shape (and, when
    given, this dtype)."""
    shape = tuple(shape)
    for a in iter_intermediate_avals(closed_jaxpr):
        if tuple(a.shape) != shape:
            continue
        if dtype is None or a.dtype == np.dtype(dtype):
            return True
    return False


def find_intermediates(closed_jaxpr,
                       pred: Callable[[object], bool]) -> list[EqnSite]:
    """Equation sites with at least one output aval satisfying ``pred`` —
    the one-touch / precision rules' workhorse (keeps provenance)."""
    out = []
    for site in iter_eqns(closed_jaxpr):
        for var in site.eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape") and pred(aval):
                out.append(site)
                break
    return out


# Pure data movement: consuming A through these is a re-index of the same
# touch, not a second pass over the data.
_DATA_MOVEMENT_PRIMS = frozenset({
    "convert_element_type", "reshape", "transpose", "broadcast_in_dim",
    "slice", "dynamic_slice", "dynamic_update_slice", "squeeze", "pad",
    "concatenate", "rev", "gather", "copy", "device_put", "stop_gradient",
    "select_n",
})


def count_a_consumers(closed_jaxpr, n: int, d: int) -> int:
    """Number of COMPUTE equations consuming an A-shaped operand — an
    operand whose trailing dims are (≥n_rows, d) for any row count ≥ n
    (covers both full A and row-sharded/padded variants; n-CHUNKED slices
    of A are excluded on purpose: the chunks of one streaming pass are one
    touch, and they enter through a `slice`, which is data movement).

    Containers (pjit/while/scan/...) are not consumers themselves — their
    bodies are walked instead, and walked PER OCCURRENCE (no sub-jaxpr
    dedup: jit caching makes P identical solve dispatches share one body
    object, and deduping them would hide P−1 passes over A). The count is
    calibration-relative: the one-touch rule compares a composed λ-grid
    graph against its single-point reference rather than asserting an
    absolute number."""

    def _is_a(aval) -> bool:
        shp = tuple(getattr(aval, "shape", ()))
        return len(shp) >= 2 and shp[-1] == d and shp[-2] >= n

    def walk(jx) -> int:
        c = 0
        for eqn in jx.eqns:
            subs = list(subjaxprs(eqn))
            if subs:
                for sub in subs:
                    c += walk(sub)
                continue
            if eqn.primitive.name in _DATA_MOVEMENT_PRIMS:
                continue
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is not None and _is_a(aval):
                    c += 1
                    break
        return c

    return walk(closed_jaxpr.jaxpr)


def eqn_provenance(eqn) -> str:
    """``file:line (primitive)`` for the user frame that created an
    equation — what makes a violation actionable."""
    name = getattr(getattr(eqn, "primitive", None), "name", "?")
    src = getattr(eqn, "source_info", None)
    frame = (None if src is None
             else source_info_util.user_frame(src.traceback))
    if frame is not None:
        return f"{frame.file_name}:{frame.start_line} ({name})"
    return f"<no source> ({name})"


def jaxpr_text(closed_jaxpr) -> str:
    """Stable pretty-print, for equation-identity comparisons (the
    ``compute_dtype="fp32" == pre-axis graph`` claim) and primitive-name
    greps that have no structured accessor."""
    return str(closed_jaxpr)
