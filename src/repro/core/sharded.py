"""Distributed (row-sharded) unified index — the production serving path.

The corpus is sharded row-wise over the ``data`` (and ``pod``) mesh axes.
Structural heredity (Thm 3.5/4.1) is what makes shard-local graphs sound:
each shard's sub-index is a valid unified graph over its rows, so shard-local
beam search + a global top-k merge is a correct (and embarrassingly parallel)
decomposition of the query.

Since DESIGN.md §12 the sharded index is the *same* :class:`IndexStore`
pytree the single-host path serves — leaves row-sharded over the index
axes, quantization parameters replicated — wrapped with the shard-local →
global id map in :class:`ShardedIndex`.  There is no separate sharded
representation anymore.

Collective schedule (see DESIGN.md §4 and EXPERIMENTS.md §Perf):

* baseline merge — one ``all_gather`` of per-shard top-k over every index
  axis, then a replicated sort;
* hierarchical merge — intra-pod ``all_gather`` + local reduce first, then
  the (slow, cross-pod) axis moves only ``k`` survivors per pod instead of
  ``k`` per chip: cross-pod bytes drop by the pod size (16×).

Construction (DESIGN.md §12): :func:`build_sharded_store` builds every
shard's graph **on device** in one jitted ``shard_map`` program — exact
KNN over the shard's own rows (the blocked scan of
``candidates.brute_force_knn``) supplies the spatial candidates,
shard-local attribute sort orders the Alg. 1 interval candidates, and the
same jitted ``_prune_all`` / repair iterations the single-host build runs
(``build.refine_candidates``) refine each shard — no per-shard host
``build_ug`` calls, no round-robin numpy padding loop, and no row staged
on one device.  :func:`build_sharded_index_host` remains as the
serial host reference the parity tests compare against.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import intervals as iv
from repro.core.build import refine_candidates
from repro.core.candidates import attribute_candidates, brute_force_knn, merge_topk
from repro.core.entry import build_entry_index, get_entry_batch_flags, get_entry_flags
from repro.core.prune import squared_dist
from repro.core.search import beam_search_flags
from repro.core.store import (
    IndexStore, VectorPlane, quantization_params, train_pq_codebooks,
)


class ShardedIndex(NamedTuple):
    """A row-sharded :class:`IndexStore` + the shard-local → global id map.

    ``store`` carries ``entry=None`` (each shard builds its entry structure
    over its own rows inside ``shard_map``) and ``alive=None`` (liveness is
    ``global_ids >= 0`` — a pad or shard-level tombstone flips the gid).
    """

    store: IndexStore
    global_ids: jnp.ndarray  # (n,) shard-local row -> global id, -1 = pad


def _plane_like(plane, row, rep):
    """A VectorPlane-shaped pytree with per-leaf values (specs/shardings)."""
    if plane is None:
        return None
    return VectorPlane(
        plane.tag, row,
        None if plane.scale is None else rep,
        None if plane.zero is None else rep,
        None if plane.codebooks is None else rep,
    )


def store_pspecs(store: IndexStore, index_axes: Sequence[str]):
    """PartitionSpec pytree of a row-sharded store: capacity-leading arrays
    over ``index_axes``, quantization parameters (int8 scale/zero, pq
    codebooks) replicated."""
    row = P(tuple(index_axes))
    rep = P()
    none_or_row = lambda a: None if a is None else row
    return IndexStore(
        plane=_plane_like(store.plane, row, rep),
        rerank=_plane_like(store.rerank, row, rep),
        intervals=row, nbrs=row, status=row,
        entry=None if store.entry is None else jax.tree.map(
            lambda _: row, store.entry),
        alive=none_or_row(store.alive), free=none_or_row(store.free),
    )


def shard_index(
    mesh: Mesh,
    index_axes: Sequence[str],
    x: np.ndarray,
    intervals: np.ndarray,
    nbrs: np.ndarray,
    status: np.ndarray,
    global_ids: np.ndarray,
    *,
    dtype: str = "f32",
    rerank: bool = False,
    qparams=None,
) -> ShardedIndex:
    """Assemble host arrays into a row-sharded :class:`ShardedIndex`.

    ``dtype``/``rerank`` encode the vector planes exactly as the single-host
    store does (core/store.py); quantization parameters are derived over
    the *real* rows only (``global_ids >= 0`` — the host builder's zero
    pad rows would otherwise widen the per-dim ranges and inflate the
    quantization error), or passed via ``qparams``, and replicated.
    """
    row = NamedSharding(mesh, P(tuple(index_axes)))
    x_d = jax.device_put(np.asarray(x), row)   # rows straight to their shards
    if dtype in ("int8", "pq") and qparams is None:
        real = np.asarray(global_ids) >= 0
        qparams = (
            quantization_params(x_d, mask=jax.device_put(real, row))
            if dtype == "int8" else train_pq_codebooks(np.asarray(x)[real])
        )
    store = IndexStore(
        plane=VectorPlane.encode(x_d, dtype, qparams),
        rerank=VectorPlane.encode(x_d, "f32") if rerank else None,
        intervals=jnp.asarray(intervals),
        nbrs=jnp.asarray(nbrs),
        status=jnp.asarray(status),
        entry=None,
    )
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), store_pspecs(store, index_axes),
        is_leaf=lambda v: isinstance(v, P),
    )
    return ShardedIndex(
        jax.device_put(store, shardings),
        jax.device_put(np.asarray(global_ids), row),
    )


def make_sharded_search_fn(
    mesh: Mesh,
    *,
    index_axes: Sequence[str] = ("data",),
    replicated_axes: Sequence[str] = ("model",),
    sem: iv.Semantics = iv.Semantics.IF,
    ef: int = 64,
    k: int = 10,
    hierarchical: bool = True,
    backend: str | None = None,
    width: int = 4,
    mixed: bool = False,
    plane_tag: str = "f32",
    has_rerank: bool = False,
):
    """Build the jittable sharded search step over a :class:`ShardedIndex`.

    Inside ``shard_map`` every device runs Alg. 5 + Alg. 4 on its rows —
    through the *same* store-based ``beam_search_flags`` the single-host
    path serves, so plane dispatch (f32/bf16/int8 + rerank) carries over
    unchanged — then the per-shard top-k are merged across the index axes.
    With ``hierarchical=True`` and 2 index axes (pod, data), the merge
    reduces intra-pod first so only ``k`` candidates per pod cross the pod
    axis.  ``backend``/``width`` select the shard-local search pipeline.

    With ``mixed=True`` the returned function takes one extra trailing
    argument — a replicated ``(B,)`` int32 sem-flag array — and the single
    compiled program serves interleaved IF/IS/RF/RS traffic (DESIGN.md §10).

    ``plane_tag``/``has_rerank`` declare the store layout the returned
    function will be called with (they fix the in_specs pytree; the actual
    kernel dispatch happens on the store's own tag).
    """
    index_axes = tuple(index_axes)

    def local_search(store: IndexStore, gids, q_v, q_int, sem_flags):
        # Rows with gids < 0 are pads OR shard-level tombstones (a streaming
        # delete flips the row's gid to -1): both are masked out of the
        # entry structure so they can never be certified by Alg. 5
        # (Lemma 4.3 soundness), and the same mask becomes the store's
        # alive mask — tombstoned rows still route traffic through their
        # edges but never surface (DESIGN.md §11).
        alive = gids >= 0
        eidx = build_entry_index(store.intervals, node_mask=alive)
        st = store.replace(entry=eidx, alive=alive)
        if backend == "legacy":
            entry = get_entry_flags(eidx, q_int, sem_flags)
        else:
            entry = get_entry_batch_flags(eidx, q_int, sem_flags, width=width)
        res = beam_search_flags(
            st, entry, q_v, q_int, sem_flags,
            ef=ef, k=k, backend=backend, width=width,
        )
        nloc = store.capacity
        g = jnp.where(res.ids >= 0, gids[jnp.clip(res.ids, 0, nloc - 1)], -1)
        return g, res.dist

    def merge_axis(ids, dist, axis_name):
        """all_gather per-shard candidates along one axis and re-reduce."""
        ga = jax.lax.all_gather(ids, axis_name, axis=1)     # (B, S, k)
        gd = jax.lax.all_gather(dist, axis_name, axis=1)
        B = ga.shape[0]
        ga = ga.reshape(B, -1)
        gd = gd.reshape(B, -1)
        order = jnp.argsort(gd, axis=-1)[:, :k]
        return (
            jnp.take_along_axis(ga, order, axis=-1),
            jnp.take_along_axis(gd, order, axis=-1),
        )

    def sharded(store, gids, q_v, q_int, sem_flags):
        ids, dist = local_search(store, gids, q_v, q_int, sem_flags)
        if hierarchical:
            # innermost (fast, intra-pod) axis first, then outer axes.
            for ax in reversed(index_axes):
                ids, dist = merge_axis(ids, dist, ax)
        else:
            ids, dist = merge_axis(
                ids, dist, index_axes if len(index_axes) > 1 else index_axes[0]
            )
        return ids, dist

    row = P(index_axes)
    rep = P()
    # The in_specs pytree mirrors the ShardedIndex layout the caller holds.
    template = IndexStore(
        plane=VectorPlane(plane_tag, None,
                          None if plane_tag != "int8" else True,
                          None if plane_tag != "int8" else True,
                          None if plane_tag != "pq" else True),
        rerank=None if not has_rerank else VectorPlane("f32", None),
        intervals=None, nbrs=None, status=None, entry=None,
    )
    store_specs = store_pspecs(template, index_axes)
    if mixed:
        def body(sidx, q_v, q_int, sem_flags):
            return sharded(sidx.store, sidx.global_ids, q_v, q_int, sem_flags)

        in_specs = (ShardedIndex(store_specs, row), rep, rep, rep)
    else:
        # Static-semantics signature: flags broadcast from ``sem``.
        def body(sidx, q_v, q_int):
            flags = jnp.full(q_v.shape[:1], sem.flag, jnp.int32)
            return sharded(sidx.store, sidx.global_ids, q_v, q_int, flags)

        in_specs = (ShardedIndex(store_specs, row), rep, rep)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(rep, rep),
        check_vma=False,
    )
    return jax.jit(fn)


def local_shard_view(sidx: ShardedIndex, s: int, n_shards: int):
    """Shard ``s``'s row block of a :class:`ShardedIndex` as a standalone
    ``(IndexStore, global_ids)`` pair.

    Row-sharded leaves partition axis 0 into equal contiguous blocks per
    shard (that is what ``P((index_axes,))`` means), so shard ``s`` is rows
    ``[s·per, (s+1)·per)``; replicated leaves (quantization parameters) are
    shared.  The view is the unit of the straggler probe
    (:func:`make_shard_probe_fns`): searching it alone reproduces exactly
    what shard ``s`` computes inside the ``shard_map`` program.
    """
    cap = sidx.store.capacity
    if cap % n_shards:
        raise ValueError(f"capacity {cap} not divisible by {n_shards} shards")
    per = cap // n_shards
    sl = slice(s * per, (s + 1) * per)
    st = sidx.store

    def cut(pl):
        if pl is None:
            return None
        # rows are sliced; quantization params (scale/zero/codebooks) are
        # replicated across shards, so they pass through shared.
        return dataclasses.replace(pl, data=pl.data[sl])

    store = IndexStore(
        plane=cut(st.plane), rerank=cut(st.rerank),
        intervals=st.intervals[sl], nbrs=st.nbrs[sl], status=st.status[sl],
        entry=None,
    )
    return store, sidx.global_ids[sl]


def make_shard_probe_fns(
    sidx: ShardedIndex,
    n_shards: int,
    *,
    ef: int = 64,
    k: int = 10,
    backend: str | None = None,
    width: int = 4,
):
    """Per-shard local-search callables for straggler probing (DESIGN.md §13).

    Shard ``s``'s callable runs the *same* shard-local program the sharded
    search step runs inside ``shard_map`` — entry structure over own rows,
    ``beam_search_flags``, gid mapping — but on shard ``s``'s row block
    alone, so timing one call isolates that shard's step cost.  The serve
    runtime's :class:`~repro.serve.runtime.FleetServeMonitor` feeds these
    timings into :class:`~repro.ft.straggler.FleetMonitor` to turn slow
    shards into mitigation recommendations and
    :func:`~repro.ft.elastic.plan_serve_rescale` replica plans.

    All shards share one compiled program (the row blocks are equal-shaped;
    the shard's arrays are call arguments, not closure constants).  Returns
    a list of ``fn(q_v, q_int, sem_flags) -> (global_ids, dist)``.
    """
    views = [local_shard_view(sidx, s, n_shards) for s in range(n_shards)]

    @jax.jit
    def probe(store, gids, q_v, q_int, sem_flags):
        alive = gids >= 0
        eidx = build_entry_index(store.intervals, node_mask=alive)
        st = store.replace(entry=eidx, alive=alive)
        if backend == "legacy":
            entry = get_entry_flags(eidx, q_int, sem_flags)
        else:
            entry = get_entry_batch_flags(eidx, q_int, sem_flags, width=width)
        res = beam_search_flags(
            st, entry, q_v, q_int, sem_flags,
            ef=ef, k=k, backend=backend, width=width,
        )
        nloc = store.capacity
        g = jnp.where(res.ids >= 0, gids[jnp.clip(res.ids, 0, nloc - 1)], -1)
        return g, res.dist

    def bind(store, gids):
        return lambda q_v, q_int, sem_flags: probe(
            store, gids, q_v, q_int, sem_flags)

    return [bind(store, gids) for store, gids in views]


# --------------------------------------------------------------------------
# Ring-streamed exact KNN (distributed candidate bootstrap)
# --------------------------------------------------------------------------
def make_ring_knn_fn(mesh: Mesh, *, axis: str = "data", k: int = 32):
    """Exact KNN graph over a row-sharded corpus via a ``ppermute`` ring.

    Each step, every shard scores its rows against the visiting column block
    and folds the result into its running top-k; the block then moves one hop
    around the ring.  After ``n_shards`` steps every pair has been scored.
    This is the sharded replacement for NN-descent bootstrap on corpora that
    exceed a single host (DESIGN.md §4).
    """

    def ring(x, gids):
        nloc = x.shape[0]
        size = jax.lax.axis_size(axis)
        perm = [(i, (i + 1) % size) for i in range(size)]

        def step(carry, _):
            blk_x, blk_ids, best_i, best_d = carry
            d = squared_dist(x, blk_x)                       # (nloc, blk)
            keep = (blk_ids[None, :] != gids[:, None]) & (blk_ids >= 0)[None, :]
            d = jnp.where(keep, d, jnp.inf)
            take = min(k, blk_x.shape[0])
            neg, idx = jax.lax.top_k(-d, take)
            cand_ids = jnp.take_along_axis(
                jnp.broadcast_to(blk_ids[None, :], d.shape), idx, axis=-1
            )
            cand_ids = jnp.where(jnp.isfinite(neg), cand_ids, -1)
            best_i, best_d = merge_topk(best_i, best_d, cand_ids, -neg, k)
            blk_x = jax.lax.ppermute(blk_x, axis, perm)
            blk_ids = jax.lax.ppermute(blk_ids, axis, perm)
            return (blk_x, blk_ids, best_i, best_d), None

        init = (
            x,
            gids,
            jnp.full((nloc, k), -1, jnp.int32),
            jnp.full((nloc, k), jnp.inf, jnp.float32),
        )
        (_, _, best_i, best_d), _ = jax.lax.scan(step, init, None, length=size)
        return best_i, best_d

    row = P((axis,))
    fn = jax.shard_map(
        ring, mesh=mesh, in_specs=(row, row), out_specs=(row, row),
        check_vma=False,
    )
    return jax.jit(fn)


# --------------------------------------------------------------------------
# Construction
# --------------------------------------------------------------------------
def _round_robin_layout(n: int, S: int):
    """Round-robin partition: shard ``s`` slot ``j`` ↔ global id ``s + j·S``
    (identical to the host reference path).  Returns the flat (S·per,) gid
    array with ``-1`` pads; at most one pad row per shard."""
    per = (n + S - 1) // S
    gid = (np.arange(S)[:, None] + np.arange(per)[None, :] * S).reshape(-1)
    return np.where(gid < n, gid, -1).astype(np.int32), per


def shard_build_fn(mesh: Mesh, cfg, *, axis: str = "data",
                   backend: str | None = None):
    """The jitted ``shard_map`` program of :func:`build_sharded_store`:
    ``(x, intervals, global_ids)`` row-sharded over ``axis`` → per-shard
    ``(nbrs, status)`` at full ``keep`` width."""

    def shard_build(xloc, ivloc, gidloc):
        valid = gidloc >= 0
        nloc = xloc.shape[0]
        # (1) spatial candidates: exact KNN over the shard's own rows.
        spa = brute_force_knn(xloc, int(cfg.ef_spatial), valid=valid).ids
        # (2) attribute candidates: shard-local Alg. 1 sort orders.
        attr = attribute_candidates(ivloc, cfg.ef_attribute)
        cand = jnp.concatenate([spa, attr], axis=1)
        self_ids = jnp.arange(nloc, dtype=jnp.int32)[:, None]
        cand = jnp.where(cand == self_ids, -1, cand)
        cand_c = jnp.clip(cand, 0, nloc - 1)
        cand = jnp.where((cand >= 0) & valid[cand_c], cand, -1)
        # (3) the jitted prune/repair iterations (same program as build_ug).
        nbrs, stat, _ = refine_candidates(xloc, ivloc, cand, cfg, backend)
        nbrs = jnp.where(valid[:, None] & (nbrs >= 0), nbrs, -1)
        stat = jnp.where(nbrs >= 0, stat, 0).astype(jnp.uint8)
        return nbrs, stat

    rowp = P((axis,))
    return jax.jit(jax.shard_map(
        shard_build, mesh=mesh, in_specs=(rowp, rowp, rowp),
        out_specs=(rowp, rowp), check_vma=False,
    ))


def build_sharded_store(
    mesh: Mesh,
    x: np.ndarray,
    intervals: np.ndarray,
    cfg,
    *,
    index_axes: Sequence[str] = ("data",),
    dtype: str = "f32",
    rerank: bool = False,
    backend: str | None = None,
) -> ShardedIndex:
    """On-device sharded build (DESIGN.md §12): one jitted ``shard_map``
    program constructs every shard's unified graph in parallel.

    Per shard: exact KNN over the shard's own rows (the blocked scan of
    :func:`~repro.core.candidates.brute_force_knn`, pad rows excluded)
    supplies the spatial candidates, shard-local attribute sort orders the
    Alg. 1 interval candidates, and ``build.refine_candidates`` — the
    *same* jitted ``_prune_all`` + repair-scatter iterations the
    single-host build runs — refines them into the final graph.  No
    per-shard host ``build_ug`` calls, no round-robin numpy padding loop:
    the host computes the O(n) round-robin permutation and places each
    shard's rows directly on its device (nothing is staged on one device),
    and makes a single device→host sync for the trailing-column trim.

    Rows partition round-robin exactly like the host reference
    (:func:`build_sharded_index_host`), so the two paths build statistically
    identical shards (the parity test pins sharded-search recall within
    0.01 across all four semantics).
    """
    if len(index_axes) != 1:
        raise NotImplementedError(
            "on-device sharded build runs over one index axis; flatten "
            "multi-axis meshes into the data axis for construction")
    axis = index_axes[0]
    S = mesh.shape[axis]
    x = np.asarray(x, np.float32)
    intervals = np.asarray(intervals)
    n = x.shape[0]
    gids, _ = _round_robin_layout(n, S)

    safe = np.clip(gids, 0, n - 1)
    real = (gids >= 0)[:, None]
    row = NamedSharding(mesh, P((axis,)))
    # Host arrays go straight to their row shards: each device receives its
    # own rows and nothing is staged on one device.
    xs_d = jax.device_put(np.where(real, x[safe], 0.0).astype(np.float32), row)
    its_d = jax.device_put(np.where(
        real, intervals[safe],
        np.asarray([2.0, -2.0], intervals.dtype),  # pads: no predicate matches
    ), row)
    gids_d = jax.device_put(gids, row)

    nbrs, stat = shard_build_fn(mesh, cfg, axis=axis, backend=backend)(
        xs_d, its_d, gids_d)

    # Single device→host sync: global trailing-column trim across shards.
    live_cols = max(int(jnp.max(jnp.sum(nbrs >= 0, axis=1))), 1)
    nbrs = jax.device_put(nbrs[:, :live_cols], row)
    stat = jax.device_put(stat[:, :live_cols], row)

    # Quantization params derive from the real rows only (zero pads would
    # widen the int8 ranges / skew the pq centroids).
    qparams = None
    if dtype == "int8":
        qparams = quantization_params(xs_d, mask=gids_d >= 0)
    elif dtype == "pq":
        qparams = train_pq_codebooks(x)
    store = IndexStore(
        plane=VectorPlane.encode(xs_d, dtype, qparams),
        rerank=VectorPlane.encode(xs_d, "f32") if rerank else None,
        intervals=its_d, nbrs=nbrs, status=stat, entry=None,
    )
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), store_pspecs(store, index_axes),
        is_leaf=lambda v: isinstance(v, P),
    )
    return ShardedIndex(jax.device_put(store, shardings), gids_d)


def build_sharded_index_host(
    x: np.ndarray,
    intervals: np.ndarray,
    n_shards: int,
    cfg,
    seed: int = 0,
):
    """Host-side reference: partition rows round-robin and build one UG per
    shard with the serial single-host builder (heredity ⇒ per-shard graphs
    are sound).  Returns per-shard arrays padded to a common width, ready
    for :func:`shard_index`.  Kept as the parity yardstick for
    :func:`build_sharded_store` (which replaces it on the hot path)."""
    from repro.core.build import build_ug

    n = x.shape[0]
    per = (n + n_shards - 1) // n_shards
    xs, its, nbs, sts, gid = [], [], [], [], []
    max_m = 1
    shards = []
    for s in range(n_shards):
        rows = np.arange(s, n, n_shards)[:per]
        g = build_ug(
            jax.random.key(seed + s), jnp.asarray(x[rows]), jnp.asarray(intervals[rows]), cfg
        )
        shards.append((rows, g))
        max_m = max(max_m, g.nbrs.shape[1])
    for rows, g in shards:
        m = g.nbrs.shape[1]
        nb = np.full((per, max_m), -1, np.int32)
        st = np.zeros((per, max_m), np.uint8)
        nloc = rows.shape[0]
        nb[:nloc, :m] = np.asarray(g.nbrs)
        st[:nloc, :m] = np.asarray(g.status)
        xpad = np.zeros((per, x.shape[1]), x.dtype)
        xpad[:nloc] = x[rows]
        ipad = np.zeros((per, 2), intervals.dtype)
        # Padded rows get inverted intervals so no predicate ever matches.
        ipad[:, 0], ipad[:, 1] = 2.0, -2.0
        ipad[:nloc] = intervals[rows]
        gpad = np.full((per,), -1, np.int32)
        gpad[:nloc] = rows
        xs.append(xpad); its.append(ipad); nbs.append(nb); sts.append(st); gid.append(gpad)
    cat = lambda arrs: np.concatenate(arrs, axis=0)
    return cat(xs), cat(its), cat(nbs), cat(sts), cat(gid)
