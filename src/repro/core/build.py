"""Iterative UG construction (paper Alg. 2) with repair sets.

Each iteration refines the candidate pool of every node by merging the
previously retained neighbors with the repair candidates produced when edges
were pruned (the pruned endpoint ``v`` is offered to its witness ``w`` so the
monotone continuation path through ``w`` can be explored next round).

TPU reformulation: repair sets are fixed-width per-node buffers filled by a
sort-by-witness + segment-rank scatter — no dynamic allocation; the pool
merge is padded-concat + dedup handled inside ``unified_prune``.

The full per-iteration sweep — blocked pruning over all ``n`` nodes plus the
repair scatter — is one jitted program: the node axis is padded to a
multiple of ``cfg.block`` and swept with ``lax.map`` (DESIGN.md §9), so the
host never re-enters the dispatch path per block and the only device→host
syncs in :func:`build_ug` are a single transfer at the end (degree stats for
``progress`` + the trailing-column trim).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.candidates import generate_candidates
from repro.core.exact import DenseGraph
from repro.core.prune import unified_prune
from repro.kernels.util import pad_rows, pad_to, segment_scatter


@dataclasses.dataclass(frozen=True)
class UGConfig:
    """Build hyper-parameters; defaults follow the paper's §5.1 (scaled names).

    Paper defaults: ef_spatial=128, ef_attribute=300, max_edges_IF =
    max_edges_IS = 256, 5 refinement iterations.
    """

    ef_spatial: int = 128
    ef_attribute: int = 300
    max_edges_if: int = 256
    max_edges_is: int = 256
    iterations: int = 5
    repair_width: int = 32          # W_max: bounded repair set per node
    alpha: float = 1.0              # RNG slack (1.0 = paper-faithful)
    unified: bool = True            # False = classical interval-agnostic RNG
    nnd_iters: int = 6
    exact_spatial: bool = False     # exact KNN candidates (small n oracle)
    block: int = 1024               # nodes pruned per jitted block
    prune_backend: str | None = None  # pallas | xla | legacy (None = platform)


def scatter_repairs(
    w_ids: jnp.ndarray, v_ids: jnp.ndarray, n: int, width: int
) -> jnp.ndarray:
    """Build fixed-width repair sets W(w) from flat (w, v) pairs (Alg. 2
    l.11-12) — the shared sort-by-segment + rank scatter
    (:func:`repro.kernels.util.segment_scatter`), kept under its Alg. 2
    name at the build layer."""
    return segment_scatter(w_ids, v_ids, n, width)


@functools.partial(jax.jit, static_argnames=("cfg", "keep", "backend"))
def _prune_all(
    x: jnp.ndarray,
    intervals: jnp.ndarray,
    cand: jnp.ndarray,
    cfg: UGConfig,
    keep: int,
    backend: str | None,
):
    """One full pruning sweep (Alg. 2 lines 8-9) over all nodes.

    A single jitted ``lax.map`` over ``cfg.block``-row tiles: no host block
    loop, no per-block dispatch.  Returns compacted neighbors/status plus the
    flat repair pairs (w, v) for :func:`scatter_repairs`.
    """
    n, C = cand.shape
    n_pad = pad_to(n, cfg.block)
    ids = jnp.arange(n_pad, dtype=jnp.int32)
    u_pad = jnp.where(ids < n, ids, 0)           # pad rows prune an empty pool
    cand_pad = pad_rows(cand, n_pad, -1)
    u_blocks = u_pad.reshape(-1, cfg.block)
    cand_blocks = cand_pad.reshape(-1, cfg.block, C)

    def one_block(args):
        u, cb = args
        res = unified_prune(
            u, cb, x, intervals,
            m_if=cfg.max_edges_if, m_is=cfg.max_edges_is,
            alpha=cfg.alpha, unified=cfg.unified, backend=backend,
        )
        # Compact retained neighbors to the front (ascending distance).
        score = jnp.where(res.status > 0, res.dist, jnp.inf)
        order = jnp.argsort(score, axis=-1)[:, :keep]
        ids_k = jnp.take_along_axis(res.order, order, axis=-1)
        st_k = jnp.take_along_axis(res.status, order, axis=-1)
        live = jnp.isfinite(jnp.take_along_axis(score, order, axis=-1))
        nbrs = jnp.where(live, ids_k, -1)
        stat = jnp.where(live, st_k, 0)
        # Repair pairs (w, v): witness gets the pruned endpoint.
        w_w = jnp.concatenate(
            [res.repair_if.reshape(-1), res.repair_is.reshape(-1)]
        )
        w_v = jnp.concatenate([
            jnp.where(res.repair_if >= 0, res.order, -1).reshape(-1),
            jnp.where(res.repair_is >= 0, res.order, -1).reshape(-1),
        ])
        return nbrs, stat, w_w, w_v

    nbrs, stat, w_w, w_v = jax.lax.map(one_block, (u_blocks, cand_blocks))
    return (
        nbrs.reshape(n_pad, keep)[:n],
        stat.reshape(n_pad, keep)[:n],
        w_w.reshape(-1),
        w_v.reshape(-1),
    )


def refine_candidates(
    x: jnp.ndarray,
    intervals: jnp.ndarray,
    cand: jnp.ndarray,
    cfg: UGConfig,
    backend: str | None = None,
):
    """The T-iteration Alg. 2 refinement over a prepared candidate pool:
    fused pruning sweep + repair-set scatter per round.

    Fully traceable (no host syncs, fixed ``keep`` width) — shared by
    :func:`build_ug` and the on-device sharded build, which runs this exact
    loop per shard under ``shard_map`` (core/sharded.py).  Returns
    ``(nbrs, stat, deg_means)`` at full ``keep`` width (untrimmed).
    """
    n = x.shape[0]
    repair = jnp.full((n, cfg.repair_width), -1, jnp.int32)
    nbrs = stat = None
    deg_means = []
    for t in range(cfg.iterations):
        pool = cand if t == 0 else jnp.concatenate([cand, repair], axis=1)
        keep = min(cfg.max_edges_if + cfg.max_edges_is, pool.shape[1])
        nbrs, stat, w_w, w_v = _prune_all(
            x, intervals, pool, cfg, keep, backend
        )
        cand = nbrs  # retained neighbors seed the next round (Alg. 2 line 10)
        if t + 1 < cfg.iterations:  # the last round's repairs feed nothing
            repair = scatter_repairs(w_w, w_v, n, cfg.repair_width)
        deg_means.append(jnp.mean(jnp.sum(nbrs >= 0, axis=1).astype(jnp.float32)))
    return nbrs, stat, jnp.stack(deg_means)


def build_ug(
    key: jax.Array,
    x: jnp.ndarray,
    intervals: jnp.ndarray,
    cfg: UGConfig = UGConfig(),
    progress: Callable[[str], None] | None = None,
) -> DenseGraph:
    """Paper Alg. 1 + Alg. 2: candidate generation then T pruning iterations.

    All iterations run on-device; degree statistics accumulate as device
    scalars and transfer to the host in a single sync after the last sweep
    (together with the trailing-column trim bound).
    """
    cand = generate_candidates(
        key, x, intervals,
        ef_spatial=cfg.ef_spatial, ef_attribute=cfg.ef_attribute,
        nnd_iters=cfg.nnd_iters, exact_spatial=cfg.exact_spatial,
    )
    if progress is not None:
        progress(f"candidates: shape {cand.shape}")

    nbrs, stat, deg_means = refine_candidates(
        x, intervals, cand, cfg, cfg.prune_backend
    )

    # Single device→host sync: per-iteration degree stats + trailing trim.
    live_cols = jnp.maximum(jnp.max(jnp.sum(nbrs >= 0, axis=1)), 1)
    live_cols, deg_host = jax.device_get((live_cols, deg_means))
    if progress is not None:
        for t, dm in enumerate(np.asarray(deg_host)):
            progress(f"iter {t + 1}/{cfg.iterations}: mean degree {float(dm):.1f}")

    return DenseGraph(nbrs[:, : int(live_cols)], stat[:, : int(live_cols)])
