"""Interval-aware beam search over the unified graph (paper Alg. 4).

TPU adaptation (DESIGN.md §2): the per-query priority queues of the paper
become a fixed-width ``(B, ef)`` beam advanced by a ``lax.while_loop``; the
visited hash-set becomes an exact per-query bitmap updated with one
deduplicated scatter-add per step; each expansion scores the neighbors of
the selected nodes through the expand-score kernel.

Two generations of the hot loop live here (DESIGN.md §8/§10):

* ``backend="legacy"`` — the original per-query ``vmap`` loop: one node
  expanded per step, full ``(ef + M)`` argsort per step;
* ``backend="pallas" | "xla"`` — the fused multi-expansion pipeline: the
  whole batch steps together, each step expands the ``W`` best unexpanded
  frontier nodes per query, scores all ``W·M`` neighbors through
  ``ops.expand_score`` (id-driven tile gather on TPU — the
  ``(B, C, d)`` candidate tensor is never materialized), dedups candidate
  ids with the sort-based ``dedup_first`` (no ``(B, C, C)`` intermediate),
  and folds them into the sorted beam with the bitonic partial-merge kernel
  (``kernels/beam_merge.py``).  The two fused backends run identical
  networks and return bit-identical ids/dists;
  :func:`search_step_memory_profile` walks one traced step to certify the
  quadratic intermediates are gone.

Query semantics are *runtime* state (DESIGN.md §10): every query carries an
int32 sem flag (``FLAG_IF`` for IF/RF, ``FLAG_IS`` for IS/RS) and
:func:`beam_search_flags` jits one program — with no static semantics
argument — that serves a mixed IF/IS/RF/RS batch.  :func:`beam_search`
(static :class:`Semantics`) is a thin wrapper over it.

Tombstones (DESIGN.md §11): ``alive`` is an optional ``(n,)`` bool mask.
Tombstoned nodes (``alive=False``) are scored and traversed exactly like
live nodes — deleting a node must not disconnect the monotone paths that
run through it — but they are filtered at result extraction, so they can
*route* and never *surface*.  ``alive=None`` (static index) skips the
masking entirely and is bit-identical to the pre-tombstone pipeline.

IndexStore (DESIGN.md §12): the public entry points take one
:class:`repro.core.store.IndexStore` pytree instead of hand-carried
``(x, intervals, nbrs, status, alive)`` tuples.  Scoring dispatches on the
store's vector-plane tag (``ops.expand_score_plane``): ``f32``/``bf16``
run the existing kernels (rows cast in-register), ``int8`` the quantized
dequant-in-register twins.  When the store carries a rerank plane, the
final beam is re-scored against the exact f32 vectors before top-k
extraction, so a quantized scan plane keeps f32-grade answers.

Trace names: :func:`search_mixed` opens the host spans ``search.flags``,
``search.entry`` and ``search.beam`` (:func:`repro.trace.span`), and each
part of the fused program sits in a ``jax.named_scope`` that names its ops
in the op metadata: ``ug.entry`` (seeding the beam from the entry ids),
``ug.select`` (ExtractMin and the neighbor-list gather), ``ug.visited``
(bitmap test and set), ``ug.predicate`` (edge status and interval
predicate), ``ug.dedup``, ``ug.score`` (``expand_score*``), ``ug.merge``
(``beam_merge``), ``ug.rerank`` and ``ug.topk`` (the final extraction).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import intervals as iv
from repro.core.entry import get_entry_batch_flags, get_entry_flags
from repro.kernels import ops
from repro.kernels.beam_merge import PAD_PAYLOAD, next_pow2
from repro.kernels.expand_score import dedup_first, dedup_first_quadratic
from repro.trace import span


class SearchResult(NamedTuple):
    ids: jnp.ndarray    # (B, k) int32 node ids, ascending distance, -1 pad
    dist: jnp.ndarray   # (B, k) f32 squared distances (+inf pad)
    steps: jnp.ndarray  # (B,) int32 expansion count (work metric for QPS)
    # () int32 shared while_loop iterations of the fused batch (None where
    # not applicable).  On lane-parallel hardware the batch-synchronous
    # latency is iterations × per-step latency (B-independent up to the lane
    # count), so this is the hardware-independent QPS signal the mixed-
    # workload benchmark models (DESIGN.md §10) — the same role the
    # comparator count plays for the merge kernel (§8).
    iters: jnp.ndarray | None = None


def frontier_width(width: int, ef: int, backend: str | None = None) -> int:
    """Frontier nodes each loop iteration expands per query: ``W`` of the
    fused backends, 1 for ``legacy``."""
    return 1 if backend == "legacy" else max(min(width, ef), 1)


def _bitmap_test(bitmap: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    word = jnp.clip(ids, 0, None) >> 5
    bit = jnp.clip(ids, 0, None) & 31
    return ((bitmap[word] >> bit) & 1).astype(bool)


def _bitmap_set(bitmap: jnp.ndarray, ids: jnp.ndarray, fresh: jnp.ndarray) -> jnp.ndarray:
    """OR the bits of ``ids[fresh]`` into the bitmap with one scatter-add.

    Neighbor lists are duplicate-free (build-time invariant) and ``fresh``
    excludes already-set bits, so add == or.
    """
    nwords = bitmap.shape[0]
    word = jnp.where(fresh, ids >> 5, nwords)  # out-of-range rows are dropped
    bit = (ids & 31).astype(jnp.uint32)
    return bitmap.at[word].add(
        jnp.where(fresh, jnp.uint32(1) << bit, jnp.uint32(0)), mode="drop"
    )


def _search_one(
    q_v: jnp.ndarray,        # (d,)
    q_int: jnp.ndarray,      # (2,)
    start: jnp.ndarray,      # () int32, -1 = no valid entry
    sem_flag: jnp.ndarray,   # () int32 FLAG_IF | FLAG_IS (runtime semantics)
    plane,                   # VectorPlane — the (n, d) scoring plane
    intervals: jnp.ndarray,  # (n, 2)
    nbrs: jnp.ndarray,       # (n, M)
    status: jnp.ndarray,     # (n, M) uint8
    ef: int,
    max_steps: int,
):
    n, d = plane.data.shape
    M = nbrs.shape[1]
    nwords = (n + 31) // 32

    q32 = q_v.astype(jnp.float32)

    def dist_to(ids):
        xs = plane.decode_rows(jnp.clip(ids, 0, n - 1)).astype(jnp.float32)
        diff = xs - q32[None, :]
        return jnp.sum(diff * diff, axis=-1)

    has_entry = start >= 0
    start_c = jnp.clip(start, 0, n - 1)

    beam_ids = jnp.full((ef,), -1, jnp.int32).at[0].set(jnp.where(has_entry, start_c, -1))
    beam_d = jnp.full((ef,), jnp.inf, jnp.float32).at[0].set(
        jnp.where(has_entry, dist_to(start_c[None])[0], jnp.inf)
    )
    expanded = jnp.zeros((ef,), bool)
    visited = jnp.zeros((nwords,), jnp.uint32)
    visited = _bitmap_set(visited, start_c[None], has_entry[None])

    def predicate(obj_int):
        return iv.predicate_by_flag(sem_flag, obj_int, q_int[None, :])

    def cond(state):
        beam_ids, beam_d, expanded, visited, steps = state
        frontier = (~expanded) & jnp.isfinite(beam_d)
        return jnp.any(frontier) & (steps < max_steps)

    def body(state):
        beam_ids, beam_d, expanded, visited, steps = state
        # ExtractMin over unexpanded beam entries (Alg. 4 line 6).
        sel_d = jnp.where(expanded, jnp.inf, beam_d)
        j = jnp.argmin(sel_d)
        u = beam_ids[j]
        expanded = expanded.at[j].set(True)
        u_c = jnp.clip(u, 0, n - 1)

        nb = nbrs[u_c]                      # (M,)
        st = status[u_c]
        present = nb >= 0
        nb_c = jnp.clip(nb, 0, n - 1)
        seen = _bitmap_test(visited, nb_c) | ~present

        sem_ok = (st.astype(jnp.int32) & sem_flag) > 0
        pred_ok = predicate(intervals[nb_c])
        valid = present & ~seen & sem_ok & pred_ok
        # Visited semantics follow the σ-projection G^σ the theory searches
        # (Thm 3.3): mark nodes that were scored (valid) or are node-level
        # dead for this query (predicate fails — can never become valid), but
        # NOT nodes skipped only because *this* edge's σ-bit is off: they may
        # be reachable via another σ-active edge.  (Deviation from Alg. 4's
        # literal line 10; see DESIGN.md §6.)
        visited = _bitmap_set(visited, nb_c, present & ~seen & (valid | ~pred_ok))
        nd = jnp.where(valid, dist_to(nb_c), jnp.inf)

        # Merge candidates into the beam; keep ef best (RemoveMax of Alg. 4).
        all_ids = jnp.concatenate([beam_ids, jnp.where(valid, nb_c, -1)])
        all_d = jnp.concatenate([beam_d, nd])
        all_exp = jnp.concatenate([expanded, jnp.zeros((M,), bool)])
        order = jnp.argsort(all_d)[:ef]
        return (
            all_ids[order],
            all_d[order],
            all_exp[order],
            visited,
            steps + 1,
        )

    state = (beam_ids, beam_d, expanded, visited, jnp.int32(0))
    beam_ids, beam_d, expanded, visited, steps = jax.lax.while_loop(cond, body, state)
    return beam_ids, beam_d, steps


def _make_fused_step(
    plane,                   # VectorPlane — the (n, d) scoring plane
    intervals: jnp.ndarray,  # (n, 2)
    nbrs: jnp.ndarray,       # (n, M)
    status: jnp.ndarray,     # (n, M) uint8
    q32: jnp.ndarray,        # (B, d) f32
    q_int: jnp.ndarray,      # (B, 2)
    sem_flags: jnp.ndarray,  # (B,) int32 runtime semantics
    *,
    W: int,
    backend: str,
):
    """Build ``(step, score, merge)`` for the fused hot loop (§8/§10).

    ``step`` advances ``(beam_d, beam_p, visited, steps)`` by one fused
    multi-expansion; it is also what :func:`search_step_memory_profile`
    traces, so the profiled program *is* the served program.  With
    ``backend="legacy"`` the step runs the pre-fusion expand/dedup pair —
    ``(B, C, d)`` gather + matmul and the ``O(C²)`` pairwise dedup — kept
    only as the A/B baseline for that profile.
    """
    n, d = plane.data.shape
    M = nbrs.shape[1]
    B = q32.shape[0]
    C = W * M

    bitmap_test = jax.vmap(_bitmap_test)
    bitmap_set = jax.vmap(_bitmap_set)
    rowi = jnp.arange(B, dtype=jnp.int32)[:, None]
    # The partial merge has no legacy variant; the legacy expand/dedup
    # profile reuses the xla merge network.
    merge_backend = "xla" if backend == "legacy" else backend
    dedup = dedup_first_quadratic if backend == "legacy" else dedup_first
    # PQ plane: the per-query (m, 256) distance tables are built HERE — once
    # per batch, before the while_loop traces — so every fused step reuses
    # one loop-invariant LUT instead of rebuilding it per step (None for
    # non-pq planes).
    lut = ops.pq_lut(plane, q32)
    # Likewise the HBM tile view the Pallas kernels gather from (a padded
    # copy of the plane when its width is not lane-aligned): once per batch.
    tiles = ops.plane_tiles(plane.data, backend)

    def score(ids_c, valid):
        """Squared distances of the masked candidate ids via the
        expand-score kernel on the store's plane (+inf where invalid)."""
        with jax.named_scope("ug.score"):
            return ops.expand_score_plane(
                plane, jnp.where(valid, ids_c, -1), q32, backend=backend,
                lut=lut, tiles=tiles,
            )

    def merge(beam_d, beam_p, cand_d, cand_p):
        with jax.named_scope("ug.merge"):
            return ops.beam_merge(beam_d, beam_p, cand_d, cand_p,
                                  backend=merge_backend)

    def step(beam_d, beam_p, visited, steps):
        with jax.named_scope("ug.select"):
            # ExtractMin_W: beam is sorted, so top_k picks the W best
            # unexpanded.
            sel_d = jnp.where((beam_p & 1) == 0, beam_d, jnp.inf)
            neg, sel_idx = jax.lax.top_k(-sel_d, W)        # (B, W)
            sel_ok = jnp.isfinite(-neg)
            u = jnp.take_along_axis(beam_p >> 1, sel_idx, axis=-1)
            mark = jnp.zeros(beam_p.shape, jnp.int32).at[rowi, sel_idx].max(
                sel_ok.astype(jnp.int32)
            )
            beam_p = beam_p | mark

            u_c = jnp.clip(u, 0, n - 1)
            nb = jnp.where(sel_ok[..., None], nbrs[u_c], -1).reshape(B, C)
            st = status[u_c].reshape(B, C)
            present = nb >= 0
            nb_c = jnp.clip(nb, 0, n - 1)
        with jax.named_scope("ug.visited"):
            seen = bitmap_test(visited, nb_c) | ~present

        with jax.named_scope("ug.predicate"):
            sem_ok = (st.astype(jnp.int32) & sem_flags[:, None]) > 0
            pred_ok = iv.predicate_by_flag(
                sem_flags[:, None], intervals[nb_c], q_int[:, None, :])
            cand_ok = present & ~seen & sem_ok & pred_ok
        # Same visited semantics as the legacy path (DESIGN.md §6): mark
        # scored and node-dead candidates, never edge-masked ones.  Across
        # the W lists one id may repeat — score/mark only its first
        # *eligible* occurrence so the scatter-add stays an OR.
        with jax.named_scope("ug.dedup"):
            valid = dedup(nb_c, cand_ok)
            to_mark = dedup(nb_c, present & ~seen & (cand_ok | ~pred_ok))
        with jax.named_scope("ug.visited"):
            visited = bitmap_set(visited, nb_c, to_mark)

        cand_d = score(nb_c, valid)
        cand_p = jnp.where(valid, nb_c << 1, PAD_PAYLOAD)
        beam_d, beam_p = merge(beam_d, beam_p, cand_d, cand_p)
        steps = steps + jnp.sum(sel_ok, axis=-1, dtype=jnp.int32)
        return beam_d, beam_p, visited, steps

    return step, score, merge


def _beam_search_fused(
    plane,                   # VectorPlane — (n, d) scoring plane
    rerank,                  # VectorPlane | None — exact f32 re-scoring plane
    intervals: jnp.ndarray,  # (n, 2)
    nbrs: jnp.ndarray,       # (n, M)
    status: jnp.ndarray,     # (n, M) uint8
    entry_ids: jnp.ndarray,  # (B, We) int32, -1 padded
    q_v: jnp.ndarray,        # (B, d)
    q_int: jnp.ndarray,      # (B, 2)
    sem_flags: jnp.ndarray,  # (B,) int32
    alive: jnp.ndarray | None,  # (n,) bool tombstone mask (None = all live)
    *,
    ef: int,
    k: int,
    max_steps: int,
    width: int,
    backend: str,
) -> SearchResult:
    """Fused multi-expansion Alg. 4 (DESIGN.md §8).

    The beam is ``E = next_pow2(ef)`` wide (padded with ``+inf``/``-1``) and
    kept ascending under the total order ``(dist, payload)``; each payload
    packs ``id << 1 | expanded``.  Every step the ``W`` best unexpanded
    entries are expanded at once; rows whose frontier is exhausted are
    natural no-ops, so the batch shares one ``while_loop`` — and because
    every per-row quantity (distances, dedup, merge, bitmap) is computed
    row-independently, each row's result is bitwise independent of the rest
    of the batch, which is what makes mixed-semantics batches return exactly
    the per-semantics answers (DESIGN.md §10).

    With a rerank plane the (possibly quantized) scan distances steer the
    traversal only; the surviving beam is re-scored against the exact f32
    plane — ``E`` row fetches per query, once — before top-k extraction.
    """
    n, d = plane.data.shape
    B = q_v.shape[0]
    W = frontier_width(width, ef)
    E = next_pow2(ef)
    nwords = (n + 31) // 32

    q32 = q_v.astype(jnp.float32)
    step, score, merge = _make_fused_step(
        plane, intervals, nbrs, status, q32, q_int, sem_flags,
        W=W, backend=backend,
    )

    # ---- seed: merge the (deduped) entry batch into an empty beam
    with jax.named_scope("ug.entry"):
        ent_valid = entry_ids >= 0
        ent_c = jnp.clip(entry_ids, 0, n - 1)
        ent_d = score(ent_c, ent_valid)
        ent_p = jnp.where(ent_valid, ent_c << 1, PAD_PAYLOAD)
        beam_d = jnp.full((B, E), jnp.inf, jnp.float32)
        beam_p = jnp.full((B, E), PAD_PAYLOAD, jnp.int32)
        beam_d, beam_p = merge(beam_d, beam_p, ent_d, ent_p)
        visited = jax.vmap(_bitmap_set)(
            jnp.zeros((B, nwords), jnp.uint32), ent_c, ent_valid
        )

    iters_cap = (max_steps + W - 1) // W

    def cond(state):
        beam_d, beam_p, visited, steps, it = state
        frontier = ((beam_p & 1) == 0) & jnp.isfinite(beam_d)
        return jnp.any(frontier) & (it < iters_cap)

    def body(state):
        beam_d, beam_p, visited, steps, it = state
        beam_d, beam_p, visited, steps = step(beam_d, beam_p, visited, steps)
        return beam_d, beam_p, visited, steps, it + 1

    state = (beam_d, beam_p, visited, jnp.zeros((B,), jnp.int32), jnp.int32(0))
    beam_d, beam_p, visited, steps, it = jax.lax.while_loop(cond, body, state)

    if rerank is not None:
        # Re-score the surviving beam against the exact f32 plane (one row
        # fetch per beam slot); the re-scored beam is no longer sorted, so
        # extraction always goes through the masked top-k.
        all_ids = beam_p >> 1
        ok = jnp.isfinite(beam_d)
        with jax.named_scope("ug.rerank"):
            beam_d = ops.expand_score(
                rerank.data, jnp.where(ok, all_ids, -1), q32, backend=backend
            )
        if alive is not None:
            ok = ok & alive[jnp.clip(all_ids, 0, n - 1)]
    elif alive is None:
        with jax.named_scope("ug.topk"):
            dist = beam_d[:, :k]                           # beam is sorted
            ids = jnp.where(jnp.isfinite(dist), beam_p[:, :k] >> 1, -1)
        return SearchResult(ids, dist, steps, it)
    else:
        # Tombstone extraction: dead beam entries routed during the loop but
        # must never surface.  The beam is sorted ascending and top_k breaks
        # ties by position, so with an all-live mask this selects exactly
        # beam[:, :k] (bit-identical to the static-index path).
        all_ids = beam_p >> 1
        ok = jnp.isfinite(beam_d) & alive[jnp.clip(all_ids, 0, n - 1)]
    with jax.named_scope("ug.topk"):
        neg, sel = jax.lax.top_k(-jnp.where(ok, beam_d, jnp.inf), k)
        dist = -neg
        ids = jnp.where(
            jnp.isfinite(dist), jnp.take_along_axis(all_ids, sel, axis=-1), -1
        )
    return SearchResult(ids, dist, steps, it)


@functools.partial(
    jax.jit, static_argnames=("ef", "k", "max_steps", "backend", "width")
)
def _beam_search_flags_impl(
    plane,                    # VectorPlane scoring plane
    rerank,                   # VectorPlane | None exact f32 plane
    intervals: jnp.ndarray,
    nbrs: jnp.ndarray,
    status: jnp.ndarray,
    alive: jnp.ndarray | None,
    entry_ids: jnp.ndarray,   # (B,) or (B, We) int32 entry node(s) (Alg. 5)
    q_v: jnp.ndarray,         # (B, d)
    q_int: jnp.ndarray,       # (B, 2)
    sem_flags: jnp.ndarray,   # (B,) int32 runtime semantics (FLAG_IF/FLAG_IS)
    *,
    ef: int,
    k: int,
    max_steps: int = 0,
    backend: str | None = None,
    width: int = 4,
) -> SearchResult:
    steps_cap = max_steps if max_steps > 0 else 8 * ef + 32
    sem_flags = sem_flags.astype(jnp.int32)
    if backend != "legacy":
        backend = ops.resolve_backend(backend)
        ent = entry_ids[:, None] if entry_ids.ndim == 1 else entry_ids
        return _beam_search_fused(
            plane, rerank, intervals, nbrs, status, ent, q_v, q_int,
            sem_flags, alive,
            ef=ef, k=k, max_steps=steps_cap, width=width, backend=backend,
        )
    entry_one = entry_ids if entry_ids.ndim == 1 else entry_ids[:, 0]
    run = jax.vmap(
        lambda qv, qi, s, f: _search_one(
            qv, qi, s, f, plane, intervals, nbrs, status,
            ef=ef, max_steps=steps_cap,
        )
    )
    beam_ids, beam_d, steps = run(q_v, q_int, entry_one, sem_flags)
    n = plane.data.shape[0]
    if rerank is not None:  # exact-plane re-scoring of the surviving beam
        ok = jnp.isfinite(beam_d) & (beam_ids >= 0)
        beam_d = ops.expand_score(
            rerank.data, jnp.where(ok, beam_ids, -1),
            q_v.astype(jnp.float32), backend=None,
        )
    if alive is not None:  # tombstoned beam entries never surface
        beam_d = jnp.where(
            (beam_ids >= 0) & alive[jnp.clip(beam_ids, 0, n - 1)],
            beam_d, jnp.inf,
        )
    top_d, top_i = jax.lax.top_k(-beam_d, k)
    ids = jnp.take_along_axis(beam_ids, top_i, axis=-1)
    dist = -top_d
    ids = jnp.where(jnp.isfinite(dist), ids, -1)
    # legacy expands one node per per-row loop step: the synchronous-batch
    # iteration equivalent is the slowest row's step count.
    return SearchResult(ids, dist, steps, jnp.max(steps))


def beam_search_flags(
    store,
    entry_ids: jnp.ndarray,   # (B,) or (B, We) int32 entry node(s) (Alg. 5)
    q_v: jnp.ndarray,         # (B, d)
    q_int: jnp.ndarray,       # (B, 2)
    sem_flags: jnp.ndarray,   # (B,) int32 runtime semantics (FLAG_IF/FLAG_IS)
    *,
    ef: int,
    k: int,
    max_steps: int = 0,
    backend: str | None = None,
    width: int = 4,
) -> SearchResult:
    """Batched Alg. 4 with *runtime* per-query semantics (DESIGN.md §10)
    over an :class:`~repro.core.store.IndexStore`.

    ``sem_flags`` is a traced ``(B,)`` array — not a static argname — so one
    compiled program serves a mixed IF/IS/RF/RS batch; ``max_steps=0``
    derives a generous default (8·ef+32).  ``backend`` selects the hot-loop
    implementation: ``"pallas"`` / ``"xla"`` are the fused multi-expansion
    pipeline (bit-identical to each other; default — pallas on TPU, xla on
    CPU), ``"legacy"`` the original one-node-per-step argsort loop.
    ``width`` is the fused frontier width W.  The store's ``alive`` mask is
    the tombstone mask (DESIGN.md §11): dead nodes route but never surface;
    its plane tag picks the scoring kernel and its rerank plane (when
    present) re-scores the final beam (DESIGN.md §12).  The store's entry
    structure is *not* consulted — entry ids come from the caller.
    """
    return _beam_search_flags_impl(
        store.plane, store.rerank, store.intervals, store.nbrs, store.status,
        store.alive, entry_ids, q_v, q_int, sem_flags,
        ef=ef, k=k, max_steps=max_steps, backend=backend, width=width,
    )


def beam_search(
    store,
    entry_ids: jnp.ndarray,
    q_v: jnp.ndarray,
    q_int: jnp.ndarray,
    *,
    sem: iv.Semantics,
    ef: int,
    k: int,
    max_steps: int = 0,
    backend: str | None = None,
    width: int = 4,
) -> SearchResult:
    """Single-semantics Alg. 4: a thin wrapper that broadcasts ``sem`` to a
    flag array and runs the same compiled program as the mixed path."""
    return beam_search_flags(
        store, entry_ids, q_v, q_int, iv.as_sem_flags(sem, q_v.shape[0]),
        ef=ef, k=k, max_steps=max_steps, backend=backend, width=width,
    )


def search_mixed(
    store,
    q_v: jnp.ndarray,
    q_int: jnp.ndarray,
    sem_flags,
    *,
    ef: int,
    k: int,
    max_steps: int = 0,
    backend: str | None = None,
    width: int = 4,
) -> SearchResult:
    """Entry acquisition (Alg. 5) + beam search (Alg. 4) for a batch whose
    queries each carry their own semantics (DESIGN.md §10).

    ``sem_flags`` accepts anything :func:`intervals.as_sem_flags` does: one
    :class:`Semantics`, a per-query sequence, or a ``(B,)`` flag array.
    The store must carry an entry structure built with a ``node_mask``
    matching its ``alive`` mask so Alg. 5 never certifies a dead node
    (UGIndex.delete maintains that invariant).
    """
    eidx = store.entry
    if eidx is None:
        raise ValueError(
            "store has no entry structure; build one (make_store/"
            "build_entry_index) or pass entry ids to beam_search_flags")
    with span("search.flags"):
        flags = iv.as_sem_flags(sem_flags, q_v.shape[0])
    with span("search.entry"):
        if backend == "legacy":
            entry_ids = get_entry_flags(eidx, q_int, flags)
        else:
            entry_ids = get_entry_batch_flags(eidx, q_int, flags, width=width)
    with span("search.beam"):
        return beam_search_flags(
            store, entry_ids, q_v, q_int, flags,
            ef=ef, k=k, max_steps=max_steps, backend=backend, width=width,
        )


def search(
    store,
    q_v: jnp.ndarray,
    q_int: jnp.ndarray,
    *,
    sem: iv.Semantics,
    ef: int,
    k: int,
    max_steps: int = 0,
    backend: str | None = None,
    width: int = 4,
) -> SearchResult:
    """Entry acquisition (Alg. 5) + interval-aware beam search (Alg. 4).

    The fused backends seed the beam with a ``width``-wide entry batch
    (widened Alg. 5) so the very first step already expands ``W`` nodes.
    """
    return search_mixed(
        store, q_v, q_int, sem,
        ef=ef, k=k, max_steps=max_steps, backend=backend, width=width,
    )


# ------------------------------------------------------------ memory profile
def search_step_memory_profile(
    backend: str,
    *,
    B: int = 8,
    n: int = 2048,
    d: int = 24,
    M: int = 16,
    width: int = 4,
    ef: int = 32,
    dtype: str = "f32",
) -> dict:
    """Trace one fused search step and report its intermediate profile.

    Returns ``{"peak_bytes", "gather_bcd", "quadratic_cc", "decoded_nd"}`` —
    whether any ``(B, C, d)`` candidate gather, ``(·, C, C)`` dedup tensor,
    or decoded ``(n, d)`` f32 corpus is materialized.  The new path
    (``xla``/``pallas``) must show none of them; the ``legacy``
    expand/dedup baseline shows the first two (the ISSUE-3 acceptance
    check, mirroring PR 2's ``sweep_memory_profile``).  ``dtype`` selects
    the vector plane: the quantized kernels carry the identical guarantee
    (DESIGN.md §12), which this profile certifies for ``int8`` too, and the
    ``pq`` LUT kernels additionally certify that scoring never decodes the
    corpus (``decoded_nd`` — only the legacy pq baseline does, DESIGN.md
    §14).
    """
    from repro.core.store import VectorPlane, default_pq_m, PQ_K
    from repro.kernels.prune_sweep import _iter_eqn_avals

    C = frontier_width(width, ef) * M
    E = next_pow2(ef)
    nwords = (n + 31) // 32
    f32, i32 = jnp.float32, jnp.int32

    def one_step(plane, intervals, nbrs, status, q_v, q_int, sem_flags,
                 beam_d, beam_p, visited, steps):
        step, _, _ = _make_fused_step(
            plane, intervals, nbrs, status, q_v.astype(f32), q_int, sem_flags,
            W=frontier_width(width, ef), backend=backend,
        )
        return step(beam_d, beam_p, visited, steps)

    if dtype == "int8":
        plane_sds = VectorPlane(
            "int8", jax.ShapeDtypeStruct((n, d), jnp.int8),
            jax.ShapeDtypeStruct((d,), f32), jax.ShapeDtypeStruct((d,), f32),
        )
    elif dtype == "pq":
        m = default_pq_m(d)
        plane_sds = VectorPlane(
            "pq", jax.ShapeDtypeStruct((n, m), jnp.uint8),
            codebooks=jax.ShapeDtypeStruct((m, PQ_K, d // m), f32),
        )
    else:
        plane_dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
        plane_sds = VectorPlane(dtype, jax.ShapeDtypeStruct((n, d), plane_dt))
    args = (
        plane_sds,
        jax.ShapeDtypeStruct((n, 2), f32),
        jax.ShapeDtypeStruct((n, M), i32),
        jax.ShapeDtypeStruct((n, M), jnp.uint8),
        jax.ShapeDtypeStruct((B, d), f32),
        jax.ShapeDtypeStruct((B, 2), f32),
        jax.ShapeDtypeStruct((B,), i32),
        jax.ShapeDtypeStruct((B, E), f32),
        jax.ShapeDtypeStruct((B, E), i32),
        jax.ShapeDtypeStruct((B, nwords), jnp.uint32),
        jax.ShapeDtypeStruct((B,), i32),
    )
    closed = jax.make_jaxpr(one_step)(*args)
    peak = 0
    gather_bcd = False
    quadratic = False
    decoded_nd = False
    for aval in _iter_eqn_avals(closed.jaxpr):
        size = int(aval.size) * aval.dtype.itemsize if aval.shape else aval.dtype.itemsize
        peak = max(peak, size)
        if len(aval.shape) >= 3 and aval.shape[-2:] == (C, d):
            gather_bcd = True
        if len(aval.shape) >= 2 and aval.shape[-2:] == (C, C):
            quadratic = True
        if len(aval.shape) >= 2 and aval.shape[-2:] == (n, d) \
                and aval.dtype == jnp.float32:
            decoded_nd = True
    return {
        "peak_bytes": peak,
        "gather_bcd": gather_bcd,
        "quadratic_cc": quadratic,
        "decoded_nd": decoded_nd,
    }


# ----------------------------------------------------------------- exact
@functools.partial(jax.jit, static_argnames=("is_filter", "k"))
def _brute_force_block(xb, ib, mb, q32, qn, q_int, ids, d, start, *, is_filter, k):
    """One jitted ground-truth block step: matmul-identity distances
    (``‖x‖²+‖q‖²−2·x·q`` at full f32 precision — no ``(nq, block, d)``
    diff tensor), predicate
    mask, exact block top-k, fold into the running top-k.  ``mb`` is the
    block's alive mask (tombstoned/free slots never enter the truth set)."""
    from repro.core.candidates import merge_topk

    xb32 = xb.astype(jnp.float32)
    xn = jnp.sum(xb32 * xb32, axis=-1)
    # HIGHEST: on a TPU a default-precision f32 matmul runs as bf16 passes,
    # which would make the recall oracle itself approximate.
    ip = jnp.dot(q32, xb32.T, precision=jax.lax.Precision.HIGHEST)
    db = jnp.maximum(qn[:, None] + xn[None, :] - 2.0 * ip, 0.0)
    if is_filter:
        ok = iv.contains(q_int[:, None, :], ib[None, :, :])
    else:
        ok = iv.contains(ib[None, :, :], q_int[:, None, :])
    db = jnp.where(ok & mb[None, :], db, jnp.inf)
    take = min(k, xb.shape[0])
    neg, idx = jax.lax.top_k(-db, take)
    bids = start + idx.astype(jnp.int32)
    return merge_topk(ids, d, bids, -neg, k)


def brute_force(
    x: jnp.ndarray,
    intervals: jnp.ndarray,
    q_v: jnp.ndarray,
    q_int: jnp.ndarray,
    *,
    sem: iv.Semantics,
    k: int,
    block: int = 8192,
    alive: jnp.ndarray | None = None,
) -> SearchResult:
    """Exact predicate-filtered top-k (ground truth for every benchmark).

    The per-block step is jitted once per block shape (full blocks share one
    program, the remainder block at most one more) and uses the matmul
    identity, so the harness's dominant cost at scale is one ``(nq, block)``
    GEMM per block instead of an untraced ``(nq, block, d)`` diff tensor.
    ``alive`` restricts the truth set to live nodes (DESIGN.md §11).
    """
    nq = q_v.shape[0]
    n = x.shape[0]
    q32 = q_v.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1)
    if alive is None:
        alive = jnp.ones((n,), bool)
    is_filter = sem in (iv.Semantics.IF, iv.Semantics.RF)
    ids = jnp.full((nq, k), -1, jnp.int32)
    d = jnp.full((nq, k), jnp.inf, jnp.float32)
    for s in range(0, n, block):
        ids, d = _brute_force_block(
            x[s : s + block], intervals[s : s + block], alive[s : s + block],
            q32, qn, q_int, ids, d, jnp.int32(s), is_filter=is_filter, k=k,
        )
    ids = jnp.where(jnp.isfinite(d), ids, -1)
    return SearchResult(ids, d, jnp.zeros((nq,), jnp.int32))
