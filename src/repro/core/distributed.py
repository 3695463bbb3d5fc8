"""Distributed (row-sharded) quadratic problems and block sketches.

Layout: A ∈ R^{n×d} is row-sharded over the mesh's data axes (the layout
backbone activations already have under DP) — batched problems shard the
row axis of each problem's (B, n, d) block, shared-A batches shard the one
(n, d) matrix. x/b/ν/Λ are replicated. Then:

* H·v      = AᵀA v + ν²Λv  — local matmuls + one psum(d) over data axes
             (or collective-free in-loop when the Gram is precomputed).
* sketch   — block sketching with *independent per-shard randomness*
             (``fold_in(key, shard_index)``), in two equivalent-in-
             expectation constructions (DESIGN.md §5):

             - **summed** (``block_sketch_gram``): SA = Σ_k S_k A_k, one
               local sketch + one psum(m×d). Because each S_k is an
               independent zero-mean embedding with E[S_kᵀS_k] = I on its
               block, E[(SA)ᵀSA] = Σ_k A_kᵀA_k = AᵀA with NO rescale —
               cross terms vanish in expectation.
             - **concatenated** (``shard_level_grams``): S = blockdiag(S_k),
               so (SA)ᵀ(SA) = Σ_k (S_k A_k)ᵀ(S_k A_k) exactly — each shard
               runs its family's one-touch ladder pass locally and the
               (L, B, d, d) level Grams are combined by ONE psum. Again no
               rescale: per-shard Gaussian entries are already N(0, 1/m),
               and SJLT/SRHT blocks satisfy E[S_kᵀS_k] = I on their block.

* factorization / iterations — replicated (m, d ≪ n).

Two execution paths, same math:

1. **GSPMD path** (production): jit the solver with A placed
   P(data_axes, None); XLA inserts the collectives. The padded adaptive
   engine takes ``mesh=`` (``sharded_padded_solve``) and swaps only its
   precompute for the explicit one-touch pass below — the in-loop hvp's
   AᵀA·v reduction is the only per-iteration collective (and none at all
   when the Gram is precomputed, the serving default).
2. **shard_map path** (explicit collectives): manual control of the
   reduction placement for the sketch+Gram hot path — ``shard_level_grams``
   is what the engine's precompute calls under ``mesh=``.

The sharded level Grams are λ-free like their single-device counterparts
(``level_grams``), so a sharded regularization path pays the SAME one
psum of the (L, B, d, d) stack for the entire λ grid
(``adaptive_padded.prepare_path_ladder(..., mesh=)`` — DESIGN.md §13);
per-λ shifted factorizations happen on the replicated Grams with no
further collectives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .level_grams import LevelGramProvider
from .precond import factorize
from .quadratic import Quadratic
from .sketches import make_sketch


def gspmd_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``: the sharded engine is a GSPMD
    program (XLA places the in-loop reductions), while ``jax.make_mesh``
    builds ``Explicit`` axes, under which a contraction over the sharded
    row axis is refused as ambiguous."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _smap(f, mesh: Mesh, in_specs, out_specs):
    """shard_map with replication checking off."""
    return jax.shard_map(f, mesh=gspmd_mesh(mesh), in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes used for data parallelism (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_data_shards(mesh: Mesh) -> int:
    """Number of row shards = product of the data-axis sizes."""
    k = 1
    for a in data_axes(mesh):
        k *= mesh.shape[a]
    return k


def _a_row_spec(q: Quadratic, mesh: Mesh) -> P:
    """PartitionSpec sharding A's row axis over the data axes."""
    da = data_axes(mesh)
    if q.batched and not q.shared_A:
        return P(None, da, None)          # (B, n, d): shard axis 1
    return P(da, None)                    # (n, d): shard axis 0


def _w_row_spec(q: Quadratic, mesh: Mesh) -> P:
    """PartitionSpec for row_weights: the row axis shards with A's."""
    da = data_axes(mesh)
    if q.batched:
        return P(None, da)                # (B, n): shard axis 1
    return P(da)                          # (n,)


def shard_quadratic(q: Quadratic, mesh: Mesh) -> Quadratic:
    """Place A (and any row_weights) row-sharded over the data axes,
    everything else replicated.

    Works for single problems, per-problem batches (B, n, d) and shared-A
    batches alike; the ``batched`` flag is preserved."""
    mesh = gspmd_mesh(mesh)
    a_sh = NamedSharding(mesh, _a_row_spec(q, mesh))
    rep = NamedSharding(mesh, P())
    w = q.row_weights
    if w is not None:
        w = jax.device_put(w, NamedSharding(mesh, _w_row_spec(q, mesh)))
    return Quadratic(
        A=jax.device_put(q.A, a_sh),
        b=jax.device_put(q.b, rep),
        nu=jax.device_put(q.nu, rep),
        lam_diag=jax.device_put(q.lam_diag, rep),
        batched=q.batched,
        row_weights=w,
    )


def _check_divisible(n: int, mesh: Mesh) -> int:
    k = n_data_shards(mesh)
    if n % k:
        raise ValueError(f"n={n} not divisible by {k} data shards")
    return k


# ---------------------------------------------------------------------------
# Sharded one-touch ladder precompute (the padded engine's mesh= path)
# ---------------------------------------------------------------------------

def shard_level_grams(
    provider: LevelGramProvider,
    keys: jax.Array,
    q: Quadratic,
    ladder: tuple[int, ...],
    mesh: Mesh,
    compute_dtype: str | None = None,
) -> jnp.ndarray:
    """(L, B, d, d) ladder-level Grams of the *concatenated* block sketch.

    Each data shard runs the family's one-touch pass — streamed gaussian /
    sjlt fold / srht FWHT — on its local row block A_k with independent
    randomness ``fold_in(keys[b], shard_index)``, producing the local
    partial Grams (S_m^{(k)} A_k)ᵀ(S_m^{(k)} A_k) at every ladder level;
    ONE psum over the data axes yields the global Grams, because the
    concatenated sketch S_m = blockdiag(S_m^{(1)}, …, S_m^{(K)}) has

        (S_m A)ᵀ(S_m A) = Σ_k (S_m^{(k)} A_k)ᵀ(S_m^{(k)} A_k)

    exactly (no cross terms), and each block is already correctly
    normalized (Gaussian entries N(0, 1/m); E[S_kᵀS_k] = I for SJLT/SRHT)
    so NO per-shard rescale is applied (DESIGN.md §5). Per shard nothing
    larger than the (L, B, d, d) Gram stack and the family's local
    O(B·m_max·d) row stream is materialized, and the psum payload is
    exactly L·B·d² per level stack.

    ``keys`` must be a (B,)-batch of per-problem keys (the engine splits a
    single key before calling); ``q`` must be batched, with n divisible by
    the data-shard count.

    ``compute_dtype`` (``kernels.precision``): each shard's one-touch pass
    runs at the reduced stream precision locally — bf16 operands / int8
    codes with fp32 accumulation — and returns fp32 partial Grams, so the
    ONE psum is an exact fp32 reduction in every mode ("bf16 passes, one
    fp32 psum"): the cross-shard sum adds no reduced-precision error.
    """
    if not q.batched:
        raise ValueError("shard_level_grams expects a batched Quadratic")
    da = data_axes(mesh)
    _check_divisible(q.n, mesh)
    m_max = ladder[-1]
    weighted = q.row_weights is not None

    def local_pass(A_blk, w_blk, b, nu, lam, ks):
        idx = jax.lax.axis_index(da)
        k_loc = jax.vmap(lambda k: jax.random.fold_in(k, idx))(ks)
        # each shard's one-touch pass sketches W^{1/2}_blk · A_blk locally:
        # the weight is row-diagonal, so it splits over row blocks exactly
        # like A does and the concatenated-block Gram identity is unchanged
        q_loc = Quadratic(A=A_blk, b=b, nu=nu, lam_diag=lam, batched=True,
                          row_weights=w_blk)
        sample_dtype = (A_blk.dtype if A_blk.dtype != jnp.int8
                        else jnp.float32)
        data = provider.sample(k_loc, m_max, A_blk.shape[-2], sample_dtype)
        g = provider.level_grams(data, q_loc, ladder,
                                 compute_dtype=compute_dtype)
        return jax.lax.psum(g, axis_name=da)

    if weighted:
        fn = _smap(
            local_pass, mesh,
            in_specs=(_a_row_spec(q, mesh), _w_row_spec(q, mesh),
                      P(), P(), P(), P()),
            out_specs=P(),
        )
        return fn(q.A, q.row_weights, q.b, q.nu, q.lam_diag, keys)
    fn = _smap(
        lambda A_blk, b, nu, lam, ks: local_pass(A_blk, None, b, nu, lam, ks),
        mesh,
        in_specs=(_a_row_spec(q, mesh), P(), P(), P(), P()),
        out_specs=P(),
    )
    return fn(q.A, q.b, q.nu, q.lam_diag, keys)


def shard_level_grams_per_shard(
    provider: LevelGramProvider,
    keys: jax.Array,
    q: Quadratic,
    ladder: tuple[int, ...],
    mesh: Mesh,
    compute_dtype: str | None = None,
) -> jnp.ndarray:
    """(K, L, B, d, d) PER-SHARD ladder-level Gram contributions — the same
    one-touch pass as ``shard_level_grams`` but all-gathered instead of
    psummed, so the caller keeps each shard's partial sum separately
    (leading axis ordered by ``axis_index``). This is the elastic-recovery
    precompute (DESIGN.md §11): the total is the exact psum result
    (``(SA)ᵀ(SA) = Σ_k (S_k A_k)ᵀ(S_k A_k)``, no cross terms), and losing
    shard k mid-solve recombines by ONE subtraction of a cached (L, B, d, d)
    stack — no surviving shard re-reads a byte of its data. Memory is K×
    the psum path's Gram stack, host-held by ``ShardLadderCache``."""
    if not q.batched:
        raise ValueError("shard_level_grams_per_shard expects a batched "
                         "Quadratic")
    da = data_axes(mesh)
    _check_divisible(q.n, mesh)
    m_max = ladder[-1]
    weighted = q.row_weights is not None

    def local_pass(A_blk, w_blk, b, nu, lam, ks):
        idx = jax.lax.axis_index(da)
        k_loc = jax.vmap(lambda k: jax.random.fold_in(k, idx))(ks)
        q_loc = Quadratic(A=A_blk, b=b, nu=nu, lam_diag=lam, batched=True,
                          row_weights=w_blk)
        sample_dtype = (A_blk.dtype if A_blk.dtype != jnp.int8
                        else jnp.float32)
        data = provider.sample(k_loc, m_max, A_blk.shape[-2], sample_dtype)
        g = provider.level_grams(data, q_loc, ladder,
                                 compute_dtype=compute_dtype)
        return g[None]                     # (1, L, B, d, d) local slice

    out_specs = P(da, None, None, None, None)
    if weighted:
        fn = _smap(
            local_pass, mesh,
            in_specs=(_a_row_spec(q, mesh), _w_row_spec(q, mesh),
                      P(), P(), P(), P()),
            out_specs=out_specs,
        )
        return fn(q.A, q.row_weights, q.b, q.nu, q.lam_diag, keys)
    fn = _smap(
        lambda A_blk, b, nu, lam, ks: local_pass(A_blk, None, b, nu, lam, ks),
        mesh,
        in_specs=(_a_row_spec(q, mesh), P(), P(), P(), P()),
        out_specs=out_specs,
    )
    return fn(q.A, q.b, q.nu, q.lam_diag, keys)


class ShardLadderCache:
    """Cached per-shard ladder-level Gram contributions + their running
    total — the state behind elastic mid-solve shard recovery.

    Built once from the SAME one-touch pass the engine would run
    (``from_mesh``: the sharded pass, all-gathered per shard;
    ``from_emulation``: the single-device ``BlockEmulationProvider``
    dataflow — identical per-shard ``fold_in(key, k)`` randomness, so the
    cache total matches the provider's summed Grams). ``total()`` feeds
    ``prepare_padded_solve(grams=…)`` / the segmented driver's ``grams=``;
    when shard k dies mid-solve, ``drop(k)`` updates the total by ONE
    (L, B, d, d) subtraction — surviving shards' data is never touched
    again — and the new total goes to ``reprecondition_padded`` via the
    driver's ``on_segment`` hook (``ft.faults.ShardLossInjector`` wires
    exactly that for the chaos suite).

    The post-drop total is the exact concatenated-block sketch Gram of the
    surviving K−1 shards: still a valid (merely weaker) preconditioner of
    the FULL problem, whose Hessian never referenced the cache at all — so
    the resumed solve's certificate stays truthful."""

    def __init__(self, shard_grams: jnp.ndarray):
        if shard_grams.ndim != 5:
            raise ValueError(
                f"expected (K, L, B, d, d) shard Grams, got shape "
                f"{tuple(shard_grams.shape)}")
        self.shard_grams = shard_grams
        self.n_shards = int(shard_grams.shape[0])
        self.alive = set(range(self.n_shards))
        # sequential accumulation in shard order — the same fp32 reduction
        # order as BlockEmulationProvider's summed pass, so the emulated
        # cache total is bit-identical to the provider's Grams
        total = shard_grams[0]
        for k in range(1, self.n_shards):
            total = total + shard_grams[k]
        self._total = total

    @classmethod
    def from_mesh(cls, provider, keys, q: Quadratic, ladder, mesh: Mesh,
                  compute_dtype: str | None = None) -> "ShardLadderCache":
        from .level_grams import get_provider

        grams = shard_level_grams_per_shard(
            get_provider(provider), keys, q, ladder, mesh,
            compute_dtype=compute_dtype)
        return cls(grams)

    @classmethod
    def from_emulation(cls, inner, keys, q: Quadratic, ladder,
                       n_shards: int,
                       compute_dtype: str | None = None) -> "ShardLadderCache":
        """Single-device build mirroring ``BlockEmulationProvider``: shard k
        sketches rows [k·n/K, (k+1)·n/K) under ``fold_in(keys, k)``."""
        from .level_grams import get_provider

        inner = get_provider(inner)
        if q.n % n_shards:
            raise ValueError(
                f"n={q.n} not divisible by {n_shards} emulated shards")
        n_loc = q.n // n_shards
        sample_dtype = q.A.dtype if q.A.dtype != jnp.int8 else jnp.float32
        w = q.row_weights
        per_shard = []
        for k in range(n_shards):
            keys_k = jax.vmap(lambda kb: jax.random.fold_in(kb, k))(keys)
            data = inner.sample(keys_k, ladder[-1], n_loc, sample_dtype)
            A_k = q.A[..., k * n_loc:(k + 1) * n_loc, :]
            w_k = None if w is None else w[:, k * n_loc:(k + 1) * n_loc]
            q_k = Quadratic(A=A_k, b=q.b, nu=q.nu, lam_diag=q.lam_diag,
                            batched=q.batched, row_weights=w_k)
            per_shard.append(inner.level_grams(
                data, q_k, ladder, compute_dtype=compute_dtype))
        return cls(jnp.stack(per_shard, axis=0))

    def total(self) -> jnp.ndarray:
        """(L, B, d, d) level Grams summed over the shards still alive."""
        return self._total

    def drop(self, k: int) -> jnp.ndarray:
        """Shard k died: remove its cached contribution from the total by
        one subtraction (no re-touch of any surviving shard's rows) and
        return the recombined (L, B, d, d) Grams."""
        if k not in self.alive:
            raise ValueError(
                f"shard {k} is not alive (alive: {sorted(self.alive)})")
        if len(self.alive) <= 1:
            raise ValueError("cannot drop the last remaining shard")
        self.alive.discard(k)
        self._total = self._total - self.shard_grams[k]
        return self._total


def shard_weighted_gram(q: Quadratic, mesh: Mesh) -> jnp.ndarray:
    """(B, d, d) AᵀWA for a row-sharded weighted batch: each shard runs the
    chunked streaming Gram (``quadratic.gram``) on its local row
    block — no (n, d) weighted copy of A anywhere — and ONE psum combines
    the block Grams (AᵀWA = Σ_k A_kᵀW_kA_k exactly: W is row-diagonal)."""
    from .quadratic import gram

    if not q.batched or q.row_weights is None:
        raise ValueError("shard_weighted_gram expects a batched, weighted "
                         "Quadratic")
    da = data_axes(mesh)
    _check_divisible(q.n, mesh)

    def local_gram(A_blk, w_blk):
        return jax.lax.psum(gram(A_blk, w_blk), axis_name=da)

    fn = _smap(local_gram, mesh,
               in_specs=(_a_row_spec(q, mesh), _w_row_spec(q, mesh)),
               out_specs=P())
    return fn(q.A, q.row_weights)


def sharded_padded_solve(q: Quadratic, keys: jax.Array, mesh: Mesh, **kw):
    """GSPMD path: place a batched problem's A over the mesh's data axes
    and run the padded adaptive engine with the sharded one-touch
    precompute (``mesh=`` swaps only the provider call; the while_loop is
    unchanged and the in-loop hvp's AᵀA·v reduction — when ``gram_hvp`` is
    off — is the only per-iteration collective)."""
    from .adaptive_padded import padded_adaptive_solve_batched

    qd = shard_quadratic(q, mesh)
    return padded_adaptive_solve_batched(qd, keys, mesh=mesh, **kw)


# ---------------------------------------------------------------------------
# Explicit shard_map path for the summed block sketch + factorize
# ---------------------------------------------------------------------------

def block_sketch_gram(
    A: jnp.ndarray,
    key: jax.Array,
    kind: str,
    m: int,
    mesh: Mesh,
    *,
    s: int = 1,
):
    """Compute SA = Σ_k S_k A_k with per-shard randomness, under shard_map.

    Returns the replicated (m, d) sketched matrix. The per-shard sketch uses
    ``jax.random.fold_in(key, shard_index)`` so shards are independent, and
    the row budget m is kept global (each shard contributes to all m rows —
    this is summing sketches, not concatenating). No rescale is applied:
    each S_k has E[S_kᵀS_k] = I on its block and the blocks are independent
    and zero-mean, so E[(SA)ᵀSA] = Σ_k A_kᵀA_k = AᵀA already. (A previous
    revision divided by √K, which shrank the sketched Gram — and therefore
    the AᵀA part of the preconditioner H_S — K-fold; the regression test in
    tests/test_sharded.py pins the corrected normalization.)
    """
    da = data_axes(mesh)
    _check_divisible(A.shape[0], mesh)

    def local_sketch(A_blk: jnp.ndarray) -> jnp.ndarray:
        idx = jax.lax.axis_index(da)
        k = jax.random.fold_in(key, idx)
        sk = make_sketch(kind, m, A_blk.shape[0], k, dtype=A_blk.dtype, s=s)
        return jax.lax.psum(sk.apply(A_blk), axis_name=da)

    fn = _smap(local_sketch, mesh, in_specs=P(da, None), out_specs=P())
    return fn(A)


def distributed_sketch_and_factorize(
    q: Quadratic, key: jax.Array, kind: str, m: int, mesh: Mesh, *, s: int = 1
):
    """Block sketch + replicated factorization of H_S."""
    SA = block_sketch_gram(q.A, key, kind, m, mesh, s=s)
    return factorize(SA, q.nu, q.lam_diag)


# ---------------------------------------------------------------------------
# GSPMD shardings (used by dryrun / launch): jit the plain Quadratic ops with
# these and XLA inserts the data-axis collectives.
# ---------------------------------------------------------------------------

def quadratic_shardings(mesh: Mesh, q: Quadratic | None = None) -> Quadratic:
    """Sharding pytree matching Quadratic: A row-sharded, rest replicated.

    Pass ``q`` to pick the batched layouts (per-problem A shards axis 1);
    without it the single-problem (n, d) layout is assumed."""
    mesh = gspmd_mesh(mesh)
    da = data_axes(mesh)
    a_spec = _a_row_spec(q, mesh) if q is not None else P(da, None)
    batched = bool(q.batched) if q is not None else False
    weighted = q is not None and q.row_weights is not None
    return Quadratic(
        A=NamedSharding(mesh, a_spec),
        b=NamedSharding(mesh, P()),
        nu=NamedSharding(mesh, P()),
        lam_diag=NamedSharding(mesh, P()),
        batched=batched,
        row_weights=(NamedSharding(mesh, _w_row_spec(q, mesh))
                     if weighted else None),
    )
