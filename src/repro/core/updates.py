"""Streaming index maintenance: batched insert / delete / repair / compact.

The paper builds the UG once (Alg. 1-3 + the Alg. 2 repair loop); a
production interval-aware service sees continuous churn — listings expire,
prices move, validity windows shift.  This module turns that lifecycle into
a jitted, batched subsystem (DESIGN.md §11):

* **slot allocator** — the :class:`~repro.core.store.IndexStore` arrays
  are sized to a power-of-two ``capacity``; ``alive`` marks live nodes,
  ``free`` the slots the allocator may hand out.  The allocator lives on
  the store (``masks``/``widen_rows``/``grow``, DESIGN.md §12); growth
  doubles capacity, so array shapes (and therefore compiled programs)
  change O(log n) times over any insert stream.  Vector planes ride
  along: new rows are encoded under each plane's frozen quantization
  parameters, pruning distances run over the best-precision f32 view
  (the rerank plane when present, else the decoded scan plane —
  identity for f32);
* **insert_batch** — one jitted program per (batch, capacity) shape:
  candidate acquisition via the *existing fused beam search* (spatial) +
  the Alg. 1 interval sort orders (attribute), ``UnifiedPrune`` for the new
  nodes' out-edges through ``ops.prune_sweep``, and reverse-edge offers
  appended under the per-semantics degree budgets as one sequential
  ``lax.scan`` over the batch (within a step the offer targets are
  distinct, so each step is one conflict-free scatter);
* **delete_batch** — tombstone the nodes (``alive=False``): search routes
  *through* them but never surfaces them (the mask threads through
  ``beam_search_flags`` result extraction and the entry structure's
  ``node_mask``).  With ``repair=True`` the iterative-repair sweep then
  re-wires every in-neighbor of a deleted node through that node's
  out-neighbors: bridge candidates (2-hop ids, scored one row at a time by
  ``ops.expand_score``, distance-truncated) run through the same Φ_IF/Φ_IS
  witness machinery (``ops.prune_sweep``) the build uses, and accepted
  bridges refill the freed degree budget — as a blocked ``lax.map`` over
  the touched rows only.  ``repair_iters > 1`` continues with Alg. 2
  rounds (witness repair sets via ``scatter_repairs``) restricted to the
  affected rows;
* **compact** — physically drops dead slots and remaps the graph.

Neither path ever re-prunes an existing edge (the PR-1 lesson: wholesale
re-pruning forgets the build's Alg. 2 repair edges and measurably degrades
old-query recall).  Inserts *append* reverse offers into free slots;
repair keeps every surviving edge verbatim and witness-filters only the
*bridges* it appends.  Appending is always sound — search masks every
traversed edge by the target's semantic bit and predicate — so extra edges
only add connectivity, and the degree budgets stay enforced.

Memory discipline matches the build and search pipelines:
:func:`update_memory_profile` walks the traced insert and repair programs
and certifies that no quadratic ``(·, C, C)`` witness/dedup tensor and no
``(B, C, d)`` bridge/search gather is ever materialized — bridge
candidates are scored one row at a time by the expand-score kernel and the
witness scan runs through the fused prune sweep.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import intervals as ivm
from repro.core.build import UGConfig, scatter_repairs
from repro.core.entry import build_entry_index, get_entry_batch_flags
from repro.core.index import UGIndex
from repro.core.prune import unified_prune
from repro.core.search import beam_search_flags
from repro.kernels import ops
from repro.kernels.beam_merge import next_pow2
from repro.kernels.expand_score import dedup_first
from repro.kernels.util import pad_to, sort_key_i32

# Query window every finite interval satisfies under IF: candidate
# acquisition searches the IF projection with this window so the fused beam
# search behaves as an unconstrained spatial ANN over the live corpus.
_WIDE = 1e30

# The slot allocator itself lives on the store (DESIGN.md §12):
# ``IndexStore.masks`` materializes the lazy alive/free masks,
# ``IndexStore.widen_rows`` restores the update-time degree headroom, and
# ``IndexStore.grow`` doubles capacity.  The pipelines below consume a
# store whose masks are already materialized.


# ------------------------------------------------------------------- insert
@functools.partial(
    jax.jit,
    static_argnames=("cfg", "backend", "search_backend", "ef", "width"),
)
def _insert_core(
    store,                               # IndexStore (masks materialized)
    new_x, new_iv, valid,                # the batch; ``valid`` masks pad rows
    *,
    cfg: UGConfig,
    backend: str | None,
    search_backend: str | None,
    ef: int,
    width: int,
):
    """One jitted insert step over a ``b``-row batch (DESIGN.md §11).

    Pad rows (``valid=False``, from the serve-path shape buckets) flow
    through every stage with sentinel slot ``cap`` and are dropped by every
    scatter — a padded batch is bitwise equal to the unpadded one.

    Candidate acquisition searches the store's *scan plane* (so a quantized
    index acquires through the same kernels it serves with); pruning and
    reverse-offer distances run over the best-precision f32 view (the
    rerank plane when present, else the decoded scan plane — identity for
    f32).  New rows are encoded into every plane under its frozen
    quantization parameters.
    """
    x = store.vectors_f32()              # pruning-precision (cap, d) f32 view
    ivs, nbrs, status = store.intervals, store.nbrs, store.status
    alive, free = store.alive, store.free
    cap, d = x.shape
    b = new_x.shape[0]
    M = nbrs.shape[1]

    # ---- slot allocation: the j-th valid row takes the j-th free slot.
    free_slots, = jnp.nonzero(free, size=b, fill_value=cap)
    rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
    slots = jnp.where(valid, free_slots[jnp.clip(rank, 0, b - 1)], cap)
    slot_c = jnp.clip(slots, 0, cap - 1)

    alive_old = alive                     # candidates = pre-insert live set
    new32 = new_x.astype(jnp.float32)
    x2 = x.at[slots].set(new32, mode="drop")
    iv2 = ivs.at[slots].set(new_iv.astype(ivs.dtype), mode="drop")
    alive2 = alive.at[slots].set(True, mode="drop")
    free2 = free.at[slots].set(False, mode="drop")

    # ---- plane updates: encode the new rows under each plane's frozen
    # parameters.  When the f32 scan plane IS the pruning view, its update
    # is exactly ``x2`` (no second scatter).
    if store.plane.tag == "f32" and store.rerank is None:
        plane2 = dataclasses.replace(store.plane, data=x2)
        rerank2 = None
    else:
        plane2 = dataclasses.replace(
            store.plane,
            data=store.plane.data.at[slots].set(
                store.plane.encode_rows(new32), mode="drop"),
        )
        rerank2 = None if store.rerank is None else dataclasses.replace(
            store.rerank, data=x2,
        )

    # ---- (1a) spatial candidates: fused beam search on the pre-insert
    # graph.  Two acquisition passes through ONE compiled program (runtime
    # semantics, DESIGN.md §10): the IF projection under a window every
    # live interval satisfies (unconstrained spatial ANN), and the IS
    # projection stabbed at the new interval's midpoint (spatially close
    # nodes that *overlap* the new node — prime IS-edge candidates).
    eidx_old = build_entry_index(ivs, node_mask=alive_old)
    wide = jnp.broadcast_to(jnp.asarray([-_WIDE, _WIDE], jnp.float32), (b, 2))
    mid = ((new_iv[:, 0] + new_iv[:, 1]) * 0.5).astype(jnp.float32)
    point = jnp.stack([mid, mid], axis=1)
    k_spa = min(cfg.ef_spatial, ef)
    spas = []
    for flag, q_int in ((ivm.FLAG_IF, wide), (ivm.FLAG_IS, point)):
        flags = jnp.full((b,), flag, jnp.int32)
        res_s = beam_search_flags(
            store,   # pre-insert store (plane, graph, tombstone mask)
            get_entry_batch_flags(eidx_old, q_int, flags, width=width),
            new32, q_int, flags,
            ef=ef, k=k_spa, backend=search_backend, width=width,
        )
        spas.append(res_s.ids)
    spa = jnp.concatenate(spas, axis=1)                    # (b, 2·k_spa)

    # ---- (1b) attribute candidates: the four Alg. 1 sort orders over the
    # live corpus (dead slots keyed +inf so they sort behind every rank).
    l_o, r_o = ivs[:, 0], ivs[:, 1]
    l_n, r_n = new_iv[:, 0], new_iv[:, 1]
    pairs = [
        (l_o, l_n), (r_o, r_n),
        ((l_o + r_o) * 0.5, (l_n + r_n) * 0.5),
        (r_o - l_o, r_n - l_n),
    ]
    n_live = jnp.sum(alive_old.astype(jnp.int32))
    w = max(cfg.ef_attribute // 8, 1)
    offs = jnp.arange(-w, w + 1)
    attrs = []
    for k_old, k_new in pairs:
        key = jnp.where(alive_old, k_old, jnp.inf)
        order = jnp.argsort(sort_key_i32(key))
        pos = jnp.searchsorted(key[order], k_new)
        attr_pos = jnp.clip(
            pos[:, None] + offs[None, :], 0, jnp.maximum(n_live - 1, 0)
        )
        attrs.append(order[attr_pos].astype(jnp.int32))
    cand = jnp.concatenate([spa.astype(jnp.int32)] + attrs, axis=1)
    c_c = jnp.clip(cand, 0, cap - 1)
    cand = jnp.where((cand >= 0) & alive_old[c_c], cand, -1)

    # ---- (2) prune the new nodes' out-edges (fused witness sweep).
    res = unified_prune(
        slot_c, cand, x2, iv2,
        m_if=cfg.max_edges_if, m_is=cfg.max_edges_is,
        alpha=cfg.alpha, unified=cfg.unified, backend=backend,
    )
    keep = min(M, res.order.shape[1])
    score = jnp.where(res.status > 0, res.dist, jnp.inf)
    sel = jnp.argsort(score, axis=1)[:, :keep]
    new_nbrs = jnp.where(
        jnp.isfinite(jnp.take_along_axis(score, sel, axis=1)),
        jnp.take_along_axis(res.order, sel, axis=1), -1,
    )
    new_stat = jnp.where(
        new_nbrs >= 0, jnp.take_along_axis(res.status, sel, axis=1), 0
    )
    if keep < M:
        new_nbrs = jnp.pad(new_nbrs, ((0, 0), (0, M - keep)), constant_values=-1)
        new_stat = jnp.pad(new_stat, ((0, 0), (0, M - keep)))
    nbrs2 = nbrs.at[slots].set(new_nbrs, mode="drop")
    status2 = status.at[slots].set(new_stat.astype(status.dtype), mode="drop")

    # ---- (3) reverse offers: u -> new appended into free slots under the
    # degree budgets, one sequential scan step per new node.  Targets are
    # the *distance-sorted candidate prefix* (2M closest), not just the
    # pruned out-neighbors — a fresh rebuild would integrate the new node
    # into those nodes' pools through the symmetric KNN of Alg. 1, and the
    # offer is the streaming approximation of that.  Within a step the
    # targets are distinct (deduped candidates), so the row/column scatters
    # are conflict-free; across steps the scan order keeps budgets exact.
    m_if, m_is = cfg.max_edges_if, cfg.max_edges_is
    k_off = min(2 * M, res.order.shape[1])
    offer_ids = res.order[:, :k_off]                       # (b, k_off)

    def offer_step(carry, inp):
        nb, st = carry
        nid, row, niv = inp              # (), (k_off,), (2,)
        u = jnp.clip(row, 0, cap - 1)
        urow = nb[u]                     # (k_off, M)
        ustat = st[u].astype(jnp.int32)
        present = (row >= 0) & (nid < cap)
        already = jnp.any(urow == nid, axis=1)
        has_free = jnp.any(urow < 0, axis=1)
        fcol = jnp.argmax(urow < 0, axis=1).astype(jnp.int32)
        live_e = urow >= 0
        cnt_if = jnp.sum(((ustat & ivm.FLAG_IF) > 0) & live_e, axis=1)
        cnt_is = jnp.sum(((ustat & ivm.FLAG_IS) > 0) & live_e, axis=1)
        iv_u = iv2[u]                    # (M, 2)
        overlap = jnp.maximum(iv_u[:, 0], niv[0]) <= jnp.minimum(iv_u[:, 1], niv[1])
        bits = (
            jnp.where(cnt_if < m_if, ivm.FLAG_IF, 0)
            | jnp.where((cnt_is < m_is) & overlap, ivm.FLAG_IS, 0)
        )
        do = present & ~already & has_free & (bits > 0)
        tgt = jnp.where(do, u, cap)
        nb = nb.at[tgt, fcol].set(nid.astype(jnp.int32), mode="drop")
        st = st.at[tgt, fcol].set(bits.astype(st.dtype), mode="drop")
        return (nb, st), None

    (nbrs2, status2), _ = jax.lax.scan(
        offer_step, (nbrs2, status2), (slots, offer_ids, new_iv)
    )

    eidx = build_entry_index(iv2, node_mask=alive2)
    out = store.replace(
        plane=plane2, rerank=rerank2, intervals=iv2, nbrs=nbrs2,
        status=status2, entry=eidx, alive=alive2, free=free2,
    )
    return out, slots


def insert_batch(
    index: UGIndex,
    new_x,
    new_intervals,
    *,
    valid=None,
    ef: int | None = None,
    width: int = 4,
    backend: str | None = None,
    search_backend: str | None = None,
) -> UGIndex:
    """Insert a batch of objects; returns a new UGIndex (functional update).

    ``valid`` masks pad rows of a shape-bucketed batch (ServeEngine.upsert);
    ``ef`` is the candidate-acquisition beam width (default
    ``max(2·ef_spatial, 48)``); ``backend`` selects the prune-sweep kernel
    and ``search_backend`` the acquisition search pipeline.

    Nodes of one batch are mutually invisible during candidate acquisition
    (candidates and offer targets come from the *pre-insert* live set, so
    the whole batch is one data-parallel jitted step).  Keep the batch
    small relative to the live corpus — ``ServeEngine.upsert`` chunks at
    half the live count so earlier chunks integrate later ones.
    """
    new_x = jnp.atleast_2d(jnp.asarray(new_x))
    new_iv = jnp.atleast_2d(jnp.asarray(new_intervals))
    b = new_x.shape[0]
    cfg = index.config
    if valid is None:
        valid = jnp.ones((b,), bool)
    else:
        valid = jnp.asarray(valid, bool)
    need = int(jnp.sum(valid))
    store = index.store.grow(need, cfg.max_edges_if + cfg.max_edges_is)
    if ef is None:
        ef = max(2 * cfg.ef_spatial, 48)
    store2, _ = _insert_core(
        store, new_x, new_iv, valid,
        cfg=cfg, backend=backend if backend is not None else cfg.prune_backend,
        search_backend=search_backend, ef=ef, width=width,
    )
    return index.with_store(store2)


def insert(index: UGIndex, new_x, new_intervals) -> UGIndex:
    """Thin wrapper kept for the PR-1 call sites: one batched insert."""
    return insert_batch(index, new_x, new_intervals)


# ------------------------------------------------------------------- delete
def _merge_repair_rows(
    u, surv_ids, surv_st, cand, x, ivs,
    *, m_if, m_is, alpha, unified, backend, M,
):
    """Conservative witness repair for a block of rows.

    Surviving edges (``surv_ids``/``surv_st``, -1 holes) are kept verbatim —
    the PR-1 lesson: re-pruning existing rows forgets the build's Alg. 2
    repair edges and measurably degrades recall.  The candidate pool
    (survivors ∪ bridges) runs through the fused Φ witness sweep so each
    *bridge* is accepted only if no closer pool member witnesses it; accepted
    bridges are appended in ascending-distance order under what remains of
    the per-semantics degree budgets.  Returns ``(nbrs_rows, stat_rows,
    w_flat, v_flat)`` with (w, v) the Alg. 2 repair pairs in global ids.
    """
    res = unified_prune(
        u, cand, x, ivs,
        m_if=m_if, m_is=m_is, alpha=alpha, unified=unified, backend=backend,
    )
    st32 = res.status.astype(jnp.int32)
    surv32 = surv_st.astype(jnp.int32)
    surv_ok = surv_ids >= 0
    # Bridge = pool member that survived the witness sweep and is not an
    # existing edge (membership is an O(P·M) integer compare — no (·,C,C)).
    is_surv = jnp.any(
        res.order[:, :, None] == jnp.where(surv_ok, surv_ids, -2)[:, None, :],
        axis=-1,
    )
    acc0 = (st32 > 0) & ~is_surv & (res.order >= 0)
    bif = acc0 & ((st32 & ivm.FLAG_IF) > 0)
    bis = acc0 & ((st32 & ivm.FLAG_IS) > 0)
    cnt_if = jnp.sum(((surv32 & ivm.FLAG_IF) > 0) & surv_ok, axis=1)
    cnt_is = jnp.sum(((surv32 & ivm.FLAG_IS) > 0) & surv_ok, axis=1)
    if_keep = bif & (jnp.cumsum(bif, axis=1) - 1 + cnt_if[:, None] < m_if)
    is_keep = bis & (jnp.cumsum(bis, axis=1) - 1 + cnt_is[:, None] < m_is)
    bits = (
        jnp.where(if_keep, ivm.FLAG_IF, 0) | jnp.where(is_keep, ivm.FLAG_IS, 0)
    )
    bridge_ids = jnp.where(bits > 0, res.order, -1)
    # Merge: survivors first (original column order and bits), then accepted
    # bridges by distance; compact the -1 holes out with one stable sort.
    ids_cat = jnp.concatenate([surv_ids, bridge_ids], axis=1)
    st_cat = jnp.concatenate([surv32, bits], axis=1)
    prio = jax.lax.broadcasted_iota(jnp.int32, ids_cat.shape, 1)
    key = jnp.where(ids_cat >= 0, prio, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key, axis=1)[:, :M]
    nb_rows = jnp.take_along_axis(ids_cat, order, axis=1)
    st_rows = jnp.take_along_axis(st_cat, order, axis=1)
    dead = jnp.take_along_axis(key, order, axis=1) == jnp.iinfo(jnp.int32).max
    nb_rows = jnp.where(dead, -1, nb_rows)
    st_rows = jnp.where(dead, 0, st_rows)
    w_flat = jnp.concatenate(
        [res.repair_if.reshape(-1), res.repair_is.reshape(-1)]
    )
    v_flat = jnp.concatenate([
        jnp.where(res.repair_if >= 0, res.order, -1).reshape(-1),
        jnp.where(res.repair_is >= 0, res.order, -1).reshape(-1),
    ])
    return nb_rows, st_rows, w_flat, v_flat


@functools.partial(
    jax.jit,
    static_argnames=("m_if", "m_is", "alpha", "unified", "backend", "P", "D",
                     "block"),
)
def _repair_core(
    x, ivs, nbrs, status, del_mask, in_sets, rows,
    *,
    m_if: int,
    m_is: int,
    alpha: float,
    unified: bool,
    backend: str | None,
    P: int,
    D: int,
    block: int,
):
    """Repair sweep round 1: re-wire the touched rows through the deleted
    nodes' neighborhoods (blocked ``lax.map``, DESIGN.md §11).

    Per touched row ``u``: pool = (surviving out-edges) ∪ (out-rows and
    in-neighbor lists of u's deleted neighbors — both sides of the deleted
    node's neighborhood, ids only), deduped with the sort-based
    ``dedup_first``, scored one row at a time by ``ops.expand_score`` (the
    ``(B, M+2MD, d)`` bridge gather is never materialized), truncated to
    the ``P`` closest, and witness-filtered by the fused Φ sweep.  ``D``
    is at least the deleted-neighbor count of every touched row, so the
    pool is ``M + 2·M·D`` wide rather than ``M + 2·M²`` — the same
    candidates, without the empty slots of live neighbors.
    """
    cap, M = nbrs.shape
    R = rows.shape[0]
    rows_c = jnp.clip(rows, 0, cap - 1)
    row_ok = rows >= 0
    tiles = ops.plane_tiles(x, backend)     # once, not per map step

    def one_block(args):
        u, ok = args                                       # (block,)
        own = nbrs[u]                                      # (block, M)
        own_c = jnp.clip(own, 0, cap - 1)
        own_del = (own >= 0) & del_mask[own_c]
        own_ids = jnp.where((own >= 0) & ~own_del, own, -1)
        own_st = jnp.where(own_ids >= 0, status[u], 0)
        # Bridge candidates: out-rows ∪ in-neighbor lists of u's deleted
        # neighbors (ids only — never gathered as vectors), in the order
        # of u's list: the first D deleted positions.
        first, slot = jax.lax.top_k(
            jnp.where(own_del, M - jnp.arange(M, dtype=jnp.int32), 0), D)
        gone = jnp.take_along_axis(own_c, slot, axis=1)          # (block, D)
        bridge = jnp.where(
            (first > 0)[:, :, None],
            jnp.concatenate([nbrs[gone], in_sets[gone]], axis=-1), -1,
        )
        bridge = bridge.reshape(u.shape[0], 2 * M * D)
        b_c = jnp.clip(bridge, 0, cap - 1)
        bridge = jnp.where((bridge >= 0) & ~del_mask[b_c], bridge, -1)
        pad = jnp.full((u.shape[0], max(P - M - 2 * M * D, 0)), -1, jnp.int32)
        cand0 = jnp.concatenate([own_ids, bridge, pad], axis=1)  # ≥ P wide
        cand0 = jnp.where(cand0 == u[:, None], -1, cand0)
        cand0 = jnp.where(dedup_first(cand0, cand0 >= 0), cand0, -1)
        # Distance-ranked pool truncation through the expand-score kernel.
        d0 = ops.expand_score(x, cand0, x[u], backend=backend, tiles=tiles)
        neg, sel = jax.lax.top_k(-d0, P)
        cand = jnp.where(
            jnp.isfinite(neg), jnp.take_along_axis(cand0, sel, axis=1), -1
        )
        nb_rows, st_rows, w_flat, v_flat = _merge_repair_rows(
            u, own_ids, own_st, cand, x, ivs,
            m_if=m_if, m_is=m_is, alpha=alpha, unified=unified,
            backend=backend, M=M,
        )
        # Untouched pad rows keep their original contents.
        nb_rows = jnp.where(ok[:, None], nb_rows, own)
        st_rows = jnp.where(ok[:, None], st_rows, status[u].astype(jnp.int32))
        # (w, v) layout is [IF half | IS half], each block-major.
        okm = jnp.tile(jnp.repeat(ok, cand.shape[1]), 2)
        w_flat = jnp.where(okm, w_flat, -1)
        return nb_rows, st_rows, w_flat, v_flat

    nb_new, st_new, w_w, w_v = jax.lax.map(
        one_block, (rows_c.reshape(-1, block), row_ok.reshape(-1, block))
    )
    nb_new = nb_new.reshape(R, M)
    st_new = st_new.reshape(R, M)
    tgt = jnp.where(row_ok, rows_c, cap)
    nbrs2 = nbrs.at[tgt].set(nb_new, mode="drop")
    status2 = status.at[tgt].set(st_new.astype(status.dtype), mode="drop")
    return nbrs2, status2, w_w.reshape(-1), w_v.reshape(-1)


@functools.partial(
    jax.jit,
    static_argnames=("m_if", "m_is", "alpha", "unified", "backend", "block"),
)
def _repair_round(
    x, ivs, nbrs, status, del_mask, repair_sets, rows,
    *,
    m_if: int,
    m_is: int,
    alpha: float,
    unified: bool,
    backend: str | None,
    block: int,
):
    """Repair rounds ≥ 2 (Alg. 2 restricted to affected rows): pool =
    current out-edges ∪ witness repair set, fused-prune, scatter back."""
    cap, M = nbrs.shape
    R = rows.shape[0]
    rows_c = jnp.clip(rows, 0, cap - 1)
    row_ok = rows >= 0

    def one_block(args):
        u, ok = args
        own = nbrs[u]
        own_ids = jnp.where(
            (own >= 0) & ~del_mask[jnp.clip(own, 0, cap - 1)], own, -1
        )
        own_st = jnp.where(own_ids >= 0, status[u], 0)
        rep = repair_sets[u]
        cand = jnp.concatenate([own_ids, rep], axis=1)
        c_c = jnp.clip(cand, 0, cap - 1)
        cand = jnp.where((cand >= 0) & ~del_mask[c_c], cand, -1)
        cand = jnp.where(cand == u[:, None], -1, cand)
        cand = jnp.where(dedup_first(cand, cand >= 0), cand, -1)
        nb_rows, st_rows, w_flat, v_flat = _merge_repair_rows(
            u, own_ids, own_st, cand, x, ivs,
            m_if=m_if, m_is=m_is, alpha=alpha, unified=unified,
            backend=backend, M=M,
        )
        nb_rows = jnp.where(ok[:, None], nb_rows, own)
        st_rows = jnp.where(ok[:, None], st_rows, status[u].astype(jnp.int32))
        okm = jnp.tile(jnp.repeat(ok, cand.shape[1]), 2)
        w_flat = jnp.where(okm, w_flat, -1)
        return nb_rows, st_rows, w_flat, v_flat

    nb_new, st_new, w_w, w_v = jax.lax.map(
        one_block, (rows_c.reshape(-1, block), row_ok.reshape(-1, block))
    )
    tgt = jnp.where(row_ok, rows_c, cap)
    nbrs2 = nbrs.at[tgt].set(nb_new.reshape(R, M), mode="drop")
    status2 = status.at[tgt].set(
        st_new.reshape(R, M).astype(status.dtype), mode="drop"
    )
    return nbrs2, status2, w_w.reshape(-1), w_v.reshape(-1)


def _pad_rows_1d(idx: np.ndarray, block: int) -> jnp.ndarray:
    """Touched-row ids padded with ``-1`` to a bucketed length: a multiple
    of ``block`` and of a quarter of the largest power of two below the
    count (at most ~25% padding), so deletes of similar size reuse one
    compiled repair program instead of compiling one per exact count."""
    n = max(idx.size, 1)
    step = max(block, (1 << (n.bit_length() - 1)) // 4)
    r = pad_to(pad_to(n, step), block)
    out = np.full((r,), -1, np.int32)
    out[: idx.size] = idx
    return jnp.asarray(out)


def deleted_in_sets(nbrs, to_del, src_rows) -> jnp.ndarray:
    """In-neighbor lists of the deleted nodes (the other half of their
    neighborhood): ``(cap, M)``, row ``v`` holding the rows whose edge
    list points at deleted ``v``, in scan order.

    ``src_rows`` are the rows holding such an edge (ascending, ``-1``
    padded).  Scattering only their edges gives the same lists as
    scattering the whole ``cap × M`` edge list — the rows keep their scan
    order — and sorts a few thousand pairs instead of ``cap·M``."""
    cap, M = nbrs.shape
    r = jnp.clip(src_rows, 0, cap - 1)
    hit = (src_rows >= 0)[:, None] & to_del[r]
    return scatter_repairs(
        jnp.where(hit, nbrs[r], -1).reshape(-1),
        jnp.where(hit, src_rows[:, None], -1).reshape(-1),
        cap, M,
    )


def repair_deleted(
    index: UGIndex,
    *,
    repair_iters: int = 1,
    pool: int | None = None,
    backend: str | None = None,
    block: int = 256,
) -> UGIndex:
    """Detach every tombstoned-but-still-routable node (DESIGN.md §11).

    Re-wires all in-neighbors of tombstoned nodes through the tombstones'
    neighborhoods: surviving edges are kept verbatim, witness-filtered
    bridges refill the freed budget, and the tombstoned rows are cleared
    and marked reusable.  ``pool`` caps the per-row candidate pool (default
    ``4·M``); ``repair_iters`` adds Alg. 2 witness-repair rounds.
    """
    store = index.store
    alive, free = store.masks()
    cfg = index.config
    cap = store.capacity
    # budget headroom for the bridges + the f32 pruning view of the vectors
    widened = store.widen_rows(cfg.max_edges_if + cfg.max_edges_is)
    nbrs, status = widened.nbrs, widened.status
    x = store.vectors_f32()
    M = nbrs.shape[1]
    del_mask = (~alive) & (~free)
    backend = backend if backend is not None else cfg.prune_backend
    kw = dict(
        m_if=cfg.max_edges_if, m_is=cfg.max_edges_is, alpha=cfg.alpha,
        unified=cfg.unified, backend=backend,
    )

    to_del = (nbrs >= 0) & del_mask[jnp.clip(nbrs, 0, cap - 1)]
    row_del = jnp.sum(to_del, axis=1, dtype=jnp.int32)
    row_del, alive_h = jax.device_get((row_del, alive))     # one host sync
    n_del = np.where(alive_h, row_del, 0)
    t_idx = np.flatnonzero(n_del)
    if t_idx.size:
        P = pool if pool is not None else min(4 * M, M + 2 * M * M)
        # deleted neighbors of the worst touched row, rounded up to a power
        # of two so similar deletes share one compiled program
        D = min(next_pow2(int(n_del.max())), M)
        rows = _pad_rows_1d(t_idx, block)
        in_sets = deleted_in_sets(
            nbrs, to_del, _pad_rows_1d(np.flatnonzero(row_del), block))
        nbrs, status, w_w, w_v = _repair_core(
            x, store.intervals, nbrs, status, del_mask, in_sets, rows,
            P=P, D=D, block=block, **kw,
        )
        for _ in range(1, repair_iters):
            rep = scatter_repairs(w_w, w_v, cap, cfg.repair_width)
            again = jnp.any(rep >= 0, axis=1) & alive
            a_idx = np.flatnonzero(np.asarray(again))
            if a_idx.size == 0:
                break
            rows = _pad_rows_1d(a_idx, block)
            nbrs, status, w_w, w_v = _repair_round(
                x, store.intervals, nbrs, status, del_mask, rep, rows,
                block=block, **kw,
            )

    # Detached: clear the dead rows and hand their slots to the allocator.
    nbrs = jnp.where(del_mask[:, None], -1, nbrs)
    status = jnp.where(del_mask[:, None], 0, status)
    return index.with_store(
        store.replace(nbrs=nbrs, status=status, free=free | del_mask)
    )


def delete_batch(
    index: UGIndex,
    ids,
    *,
    repair: bool = True,
    repair_iters: int = 1,
    pool: int | None = None,
    backend: str | None = None,
    block: int = 256,
) -> UGIndex:
    """Delete a batch of node ids; returns a new UGIndex (functional update).

    The nodes are tombstoned immediately (search routes through them but
    never surfaces them; the entry structure re-certifies over live nodes).
    With ``repair=True`` (default) the iterative-repair sweep then detaches
    them so their slots are reusable; ``repair=False`` defers that to a
    later :func:`repair_deleted` or :func:`compact` (cheap deletes, slight
    search overhead while tombstones accumulate).
    """
    ids = jnp.atleast_1d(jnp.asarray(ids, jnp.int32))
    alive, free = index.store.masks()
    cap = index.store.capacity
    tgt = jnp.where(ids >= 0, ids, cap)
    del_mask = jnp.zeros((cap,), bool).at[tgt].set(True, mode="drop") & alive
    alive2 = alive & ~del_mask
    out = index.with_store(index.store.replace(
        entry=build_entry_index(index.store.intervals, node_mask=alive2),
        alive=alive2, free=free,
    ))
    if repair:
        out = repair_deleted(
            out, repair_iters=repair_iters, pool=pool, backend=backend,
            block=block,
        )
    return out


# ------------------------------------------------------------------ compact
def compact(index: UGIndex) -> UGIndex:
    """Physically drop dead slots: gather live rows, remap neighbor ids,
    re-trim the trailing all-dead columns (undoing the update-time row
    widening), rebuild the entry structure.  Returns a static UGIndex.

    Unrepaired tombstones (from ``delete(..., repair=False)``) are still
    routable, so dropping them here without bridging would sever the
    monotone paths through them — compact therefore runs the repair sweep
    first when any exist.
    """
    if index.alive is None:
        return index
    alive0, free0 = index.store.masks()
    if bool(jnp.any((~alive0) & (~free0))):
        index = repair_deleted(index)
    store = index.store
    cap = store.capacity
    live = np.asarray(store.alive)
    old_ids = np.flatnonzero(live)
    remap = np.full((cap,), -1, np.int32)
    remap[old_ids] = np.arange(old_ids.size, dtype=np.int32)
    nb = np.asarray(store.nbrs)[old_ids]
    st = np.asarray(store.status)[old_ids]
    nb2 = np.where(nb >= 0, remap[np.clip(nb, 0, cap - 1)], -1)
    st2 = np.where(nb2 >= 0, st, 0)
    order = np.argsort(nb2 < 0, axis=1, kind="stable")  # holes to the back
    nb2 = np.take_along_axis(nb2, order, axis=1)
    st2 = np.take_along_axis(st2, order, axis=1)
    live_cols = max(int((nb2 >= 0).sum(axis=1).max()) if nb2.size else 1, 1)
    nb2, st2 = nb2[:, :live_cols], st2[:, :live_cols]
    rows = jnp.asarray(old_ids)
    ivs = store.intervals[rows]
    gather_plane = lambda p: None if p is None else dataclasses.replace(
        p, data=p.data[rows]
    )
    return index.with_store(store.replace(
        plane=gather_plane(store.plane), rerank=gather_plane(store.rerank),
        intervals=ivs,
        nbrs=jnp.asarray(nb2), status=jnp.asarray(st2.astype(st.dtype)),
        entry=build_entry_index(ivs), alive=None, free=None,
    ))


# ----------------------------------------------------------- memory profile
def update_memory_profile(
    backend: str,
    *,
    b: int = 8,
    cap: int = 1024,
    d: int = 16,
    M: int = 16,
    P: int = 48,   # ≠ the pallas sweep's bb=32 row tile (a (bb, C) working
    width: int = 4,  # row would otherwise read as a square (P, P) tensor)
    ef: int = 32,
) -> dict:
    """Trace one insert step and one repair sweep; report their intermediate
    profile (the ISSUE-4 acceptance check, à la ``search_step_memory_profile``).

    Returns ``{"peak_bytes", "quadratic_cc", "gather_bcd"}``:

    * ``quadratic_cc`` — any square ``(·, C, C)`` tensor over the insert
      candidate-pool width, the search candidate width ``W·M``, the repair
      pool ``P``, or the raw bridge width ``M+M²`` (witness matrices,
      pairwise dedup);
    * ``gather_bcd`` — a ``(·, W·M, d)`` search gather or ``(·, M+M², d)``
      bridge gather.  The ``(·, P, d)`` / ``(·, C_pool, d)`` row gathers
      feeding the prune sweep are its kernel inputs (DESIGN.md §9) and are
      allowed.

    ``backend="xla" | "pallas"`` must show neither; ``"legacy"`` routes the
    pre-fusion prune/expand baselines and shows both.
    """
    from repro.core.store import IndexStore, VectorPlane
    from repro.kernels.prune_sweep import _iter_eqn_avals

    f32, i32 = jnp.float32, jnp.int32
    cfg = UGConfig(
        ef_spatial=16, ef_attribute=32, max_edges_if=M, max_edges_is=M,
        iterations=1, repair_width=8, exact_spatial=True,
    )
    k_spa = min(cfg.ef_spatial, ef)
    w = max(cfg.ef_attribute // 8, 1)
    c_pool = 2 * k_spa + 4 * (2 * w + 1)  # insert candidate-pool width
    c_search = max(min(width, ef), 1) * M  # fused search candidate width
    c_bridge = M + 2 * M * M               # raw repair bridge width

    store_sds = IndexStore(
        plane=VectorPlane("f32", jax.ShapeDtypeStruct((cap, d), f32)),
        rerank=None,
        intervals=jax.ShapeDtypeStruct((cap, 2), f32),
        nbrs=jax.ShapeDtypeStruct((cap, M), i32),
        status=jax.ShapeDtypeStruct((cap, M), jnp.uint8),
        entry=None,
        alive=jax.ShapeDtypeStruct((cap,), jnp.bool_),
        free=jax.ShapeDtypeStruct((cap,), jnp.bool_),
    )
    insert_args = (
        store_sds,
        jax.ShapeDtypeStruct((b, d), f32),
        jax.ShapeDtypeStruct((b, 2), f32),
        jax.ShapeDtypeStruct((b,), jnp.bool_),
    )
    ins = jax.make_jaxpr(
        functools.partial(
            _insert_core, cfg=cfg, backend=backend,
            search_backend=backend, ef=ef, width=width,
        )
    )(*insert_args)

    repair_args = (
        jax.ShapeDtypeStruct((cap, d), f32),
        jax.ShapeDtypeStruct((cap, 2), f32),
        jax.ShapeDtypeStruct((cap, M), i32),
        jax.ShapeDtypeStruct((cap, M), jnp.uint8),
        jax.ShapeDtypeStruct((cap,), jnp.bool_),   # del_mask
        jax.ShapeDtypeStruct((cap, M), i32),       # in_sets
        jax.ShapeDtypeStruct((b,), i32),           # rows
    )
    rep = jax.make_jaxpr(
        functools.partial(
            _repair_core, m_if=M, m_is=M, alpha=1.0, unified=True,
            backend=backend, P=P, D=M, block=b,
        )
    )(*repair_args)

    banned_sq = {c_pool, c_search, c_bridge, P}
    peak = 0
    quadratic = False
    gather = False
    for closed in (ins, rep):
        for aval in _iter_eqn_avals(closed.jaxpr):
            size = (
                int(aval.size) * aval.dtype.itemsize
                if aval.shape else aval.dtype.itemsize
            )
            peak = max(peak, size)
            if (
                len(aval.shape) >= 2
                and aval.shape[-1] == aval.shape[-2]
                and aval.shape[-1] in banned_sq
            ):
                quadratic = True
            if len(aval.shape) >= 3 and aval.shape[-2:] in (
                (c_search, d), (c_bridge, d),
            ):
                gather = True
    return {"peak_bytes": peak, "quadratic_cc": quadratic, "gather_bcd": gather}
