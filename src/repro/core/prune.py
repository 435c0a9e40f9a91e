"""Unified interval-aware pruning (paper Alg. 3 / Def. 3.1).

The single routine :func:`unified_prune` implements the paper's
``UnifiedPrune`` for a *block* of nodes at once.  It is the workhorse of both

* the **exact URNG reference** (candidate set = all other nodes, ``M = n``,
  which is precisely Def. 3.1 evaluated in per-node ascending-distance order),
* the **practical UG build** (bounded candidate pools from Alg. 1 + repair
  sets from Alg. 2).

This module owns the fixed-shape *preprocessing* — dedup, distance sort,
vector/interval gathers — and hands the scan itself to
``ops.prune_sweep`` (kernels/prune_sweep.py), which dispatches between the
fused Pallas kernel, its bit-identical plain-XLA twin, and the legacy
materialize-everything baseline (DESIGN.md §9).  Classical RNG pruning
(used by the post-filtering baseline) is the same routine with the semantic
witness conditions forced to ``True`` (``unified=False``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import intervals as iv
from repro.kernels import ops


class PruneResult(NamedTuple):
    """Per-node pruning output, aligned to distance-sorted candidate order."""

    order: jnp.ndarray      # (B, C) int32 candidate ids sorted by δ(u, ·); -1 pad
    dist: jnp.ndarray       # (B, C) f32 squared distance to u (+inf for pads)
    status: jnp.ndarray     # (B, C) uint8 semantic bitmask (0 = fully pruned)
    repair_if: jnp.ndarray  # (B, C) int32 global id of the IF witness or -1
    repair_is: jnp.ndarray  # (B, C) int32 global id of the IS witness or -1


def squared_dist(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Blocked ‖a−b‖² via the matmul identity, in full f32 (``HIGHEST``).

    A TPU's default f32 matmul rounds its operands to bf16, and through the
    identity's cancellation that error is larger than the gap between
    consecutive neighbours; ``HIGHEST`` keeps these distances exact for
    every caller (the prune, NN-descent, the sharded ring's KNN).  On the
    CPU it changes nothing."""
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    an = jnp.sum(a32 * a32, axis=-1)
    bn = jnp.sum(b32 * b32, axis=-1)
    ip = jnp.einsum("...id,...jd->...ij", a32, b32, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    d = an[..., :, None] + bn[..., None, :] - 2.0 * ip
    return jnp.maximum(d, 0.0)


def _dedup_sorted_by_distance(cand: jnp.ndarray, dist: jnp.ndarray):
    """Mask duplicate candidate ids (keep the closest copy), then sort by
    distance.

    ``cand`` is (C,) int32 with -1 padding; ``dist`` is (C,) f32.  Among
    copies of the same id the minimum-distance one survives (ties broken by
    scan position); masked copies and -1 pads sort to the back as +inf.
    """
    big = jnp.float32(jnp.inf)
    invalid = cand < 0
    dist = jnp.where(invalid, big, dist)
    # Detect duplicates by sorting (id, dist) lexicographically and flagging
    # repeats: the first copy in that order is the closest one.
    id_order = jnp.lexsort((dist, cand))
    sorted_ids = cand[id_order]
    dup_sorted = jnp.concatenate(
        [jnp.zeros((1,), bool), sorted_ids[1:] == sorted_ids[:-1]]
    ) & (sorted_ids >= 0)
    dup = jnp.zeros_like(dup_sorted).at[id_order].set(dup_sorted)
    dist = jnp.where(dup, big, dist)
    order = jnp.argsort(dist)
    out_d = dist[order]
    # Dead slots (pads, masked duplicates) are normalized to -1 so junk ids
    # can never leak into neighbor lists downstream.
    out_c = jnp.where(jnp.isfinite(out_d), cand[order], -1)
    return out_c, out_d


@functools.partial(
    jax.jit,
    static_argnames=("m_if", "m_is", "alpha", "unified", "backend"),
)
def unified_prune(
    u_ids: jnp.ndarray,     # (B,) int32 node ids of this block
    cand: jnp.ndarray,      # (B, C) int32 candidate ids, -1 padded
    x: jnp.ndarray,         # (n, d) corpus vectors
    intervals: jnp.ndarray, # (n, 2) corpus intervals
    *,
    m_if: int,
    m_is: int,
    alpha: float = 1.0,
    unified: bool = True,
    backend: str | None = None,
) -> PruneResult:
    """Vectorized Alg. 3 over a block of ``B`` nodes.

    Returns neighbor sets in ascending-distance order together with the
    semantic bitmask of every surviving edge and the repair pairs ``(w, v)``
    feeding Alg. 2's next iteration.  ``backend`` selects the sweep
    implementation (``pallas`` / ``xla`` / ``legacy``, default per platform);
    all three are bit-identical.
    """
    B, C = cand.shape
    safe_cand = jnp.clip(cand, 0, x.shape[0] - 1)
    xu = x[u_ids]                                # (B, d)
    xc = x[safe_cand]                            # (B, C, d)
    d_uc = squared_dist(xu[:, None, :], xc)[:, 0, :]       # (B, C)
    # Exclude self-edges and padding before sorting.
    d_uc = jnp.where((cand < 0) | (cand == u_ids[:, None]), jnp.inf, d_uc)
    cand_sorted, d_sorted = jax.vmap(_dedup_sorted_by_distance)(cand, d_uc)

    safe_sorted = jnp.clip(cand_sorted, 0, x.shape[0] - 1)
    xs = x[safe_sorted].astype(jnp.float32)      # (B, C, d)
    i_c = intervals[safe_sorted]                 # (B, C, 2)
    i_u = intervals[u_ids]                       # (B, 2)

    valid = (cand_sorted >= 0) & jnp.isfinite(d_sorted)
    if unified:
        overlap = ~iv.is_empty(iv.intersection(i_u[:, None, :], i_c))
    else:
        overlap = jnp.ones((B, C), bool)

    status, rep_if, rep_is = ops.prune_sweep(
        i_u, xs, i_c, d_sorted, valid, overlap,
        m_if=m_if, m_is=m_is, alpha=alpha, unified=unified, backend=backend,
    )

    # Map local witness slots to global candidate ids.
    def to_global(rep):
        g = jnp.take_along_axis(cand_sorted, jnp.clip(rep, 0, C - 1), axis=-1)
        return jnp.where(rep >= 0, g, -1)

    return PruneResult(
        cand_sorted, d_sorted, status.astype(jnp.uint8),
        to_global(rep_if), to_global(rep_is),
    )
