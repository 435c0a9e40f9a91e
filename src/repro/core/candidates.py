"""Initial candidate generation for UG (paper Alg. 1).

Two complementary sources, exactly as the paper prescribes:

* **spatial** candidates from NN-descent with budget ``ef_spatial`` — the
  navigational backbone;
* **attribute** candidates from the four interval-derived sort keys
  ``{l, r, mid, len}``, taking ``ef_attribute / 8`` adjacent nodes per side
  per key — likely IF/IS witnesses under interval constraints.

The NN-descent here is a TPU-style reformulation: fixed-width neighbor
tensors, the local join expressed as blocked gathers + matmul distances, and
reverse edges recovered with a sort/segment-rank scatter (no dynamic lists).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.prune import squared_dist
from repro.kernels.util import pad_rows, pad_to, segment_scatter, sort_key_i32


class KnnState(NamedTuple):
    ids: jnp.ndarray    # (n, K) int32 neighbor ids, ascending distance, -1 pad
    dist: jnp.ndarray   # (n, K) f32 squared distances (+inf pad)


def merge_topk(ids_a, d_a, ids_b, d_b, k: int):
    """Merge two candidate lists per row, dedup ids, keep the k closest."""
    ids = jnp.concatenate([ids_a, ids_b], axis=-1)
    d = jnp.concatenate([d_a, d_b], axis=-1)
    d = jnp.where(ids < 0, jnp.inf, d)
    # Dedup: sort by id, mask repeats, undo permutation.
    io = jnp.argsort(ids, axis=-1)
    si = jnp.take_along_axis(ids, io, axis=-1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros_like(si[..., :1], bool), (si[..., 1:] == si[..., :-1]) & (si[..., 1:] >= 0)],
        axis=-1,
    )
    dup = jnp.zeros_like(dup_sorted)
    dup = jnp.put_along_axis(dup, io, dup_sorted, axis=-1, inplace=False)
    d = jnp.where(dup, jnp.inf, d)
    order = jnp.argsort(d, axis=-1)[..., :k]
    out_ids = jnp.take_along_axis(ids, order, axis=-1)
    out_d = jnp.take_along_axis(d, order, axis=-1)
    out_ids = jnp.where(jnp.isfinite(out_d), out_ids, -1)
    return out_ids, out_d


def brute_force_knn(
    x: jnp.ndarray,
    k: int,
    block: int = 2048,
    x_block: int = 4096,
    valid: jnp.ndarray | None = None,
) -> KnnState:
    """Exact KNN graph (self excluded) as one program: ``lax.map`` over
    ``block``-row query tiles, each a ``lax.scan`` over ``x_block``-row
    corpus chunks (matmul distances, chunk top-k, merge into the running
    top-k).  ``valid`` (``(n,)`` bool) drops rows from the candidates, as
    the pad rows of a shard.

    The row norms are computed before the scan, as separate operations: a
    norm fused into the distance program rounds differently on the CPU, and
    this way the graph equals the one the earlier block-by-block code built,
    bit for bit."""
    x32 = x.astype(jnp.float32)
    norms = jnp.sum(x32 * x32, axis=-1)
    return _knn_scan(x32, norms, valid, k=k, block=block, x_block=x_block)


@functools.partial(jax.jit, static_argnames=("k", "block", "x_block"))
def _knn_scan(x, norms, valid, *, k: int, block: int, x_block: int) -> KnnState:
    """The scan of :func:`brute_force_knn`.  Full tiles and chunks go
    through ``lax.map``/``lax.scan``; a ragged last tile or chunk keeps its
    own shape rather than being padded, because the CPU's matmul rounds
    differently at another width."""
    n, d = x.shape
    ok = jnp.ones((n,), bool) if valid is None else valid

    def split(arrays, size):
        """(full blocks stacked on a new leading axis, ragged tail, starts)"""
        full = (n // size) * size
        stacked = tuple(a[:full].reshape((-1, size) + a.shape[1:]) for a in arrays)
        tail = tuple(a[full:] for a in arrays) if full < n else None
        return stacked, tail, jnp.arange(0, full, size, dtype=jnp.int32), full

    chunks, last_chunk, c_starts, c_full = split((x, norms, ok), x_block)

    def one_tile(q, qn, q0):
        qid = q0 + jnp.arange(q.shape[0], dtype=jnp.int32)

        def chunk(state, c):
            xb, xn, okb, s = c
            cid = s + jnp.arange(xb.shape[0], dtype=jnp.int32)
            ip = jnp.einsum("id,jd->ij", q, xb, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)  # see squared_dist
            db = jnp.maximum(qn[:, None] + xn[None, :] - 2.0 * ip, 0.0)
            db = jnp.where(okb[None, :] & (cid != qid[:, None]), db, jnp.inf)
            neg, idx = jax.lax.top_k(-db, min(k, xb.shape[0]))
            # Chunks hold disjoint ids, so the merge is one top-k over the
            # running list followed by the chunk's (ties keep that order).
            ids = jnp.concatenate([state[0], cid[idx]], axis=1)
            neg, sel = jax.lax.top_k(
                jnp.concatenate([-state[1], neg], axis=1), k)
            ids = jnp.take_along_axis(ids, sel, axis=1)
            return (jnp.where(jnp.isfinite(neg), ids, -1), -neg), None

        state = (jnp.full((q.shape[0], k), -1, jnp.int32),
                 jnp.full((q.shape[0], k), jnp.inf, jnp.float32))
        if c_full:
            state = jax.lax.scan(chunk, state, chunks + (c_starts,))[0]
        if last_chunk is not None:
            state = chunk(state, last_chunk + (jnp.int32(c_full),))[0]
        return state

    tiles, last_tile, t_starts, t_full = split((x, norms), block)
    ids, dist = [], []
    if t_full:
        i, dd = jax.lax.map(lambda t: one_tile(*t), tiles + (t_starts,))
        ids.append(i.reshape(t_full, k))
        dist.append(dd.reshape(t_full, k))
    if last_tile is not None:
        i, dd = one_tile(*last_tile, jnp.int32(t_full))
        ids.append(i)
        dist.append(dd)
    return KnnState(jnp.concatenate(ids), jnp.concatenate(dist))


def _reverse_candidates(ids: jnp.ndarray, r_max: int) -> jnp.ndarray:
    """Reverse edges: for each edge u→v, offer u to v — the shared
    sort-by-segment + rank scatter (``kernels.util.segment_scatter``)."""
    n, k = ids.shape
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k)).reshape(-1)
    return segment_scatter(ids.reshape(-1), src, n, r_max)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _blocked_refine(
    x: jnp.ndarray,
    ids: jnp.ndarray,     # (n, k) current neighbor state, -1 pads
    dist: jnp.ndarray,    # (n, k)
    cand: jnp.ndarray,    # (n, Cc) join candidates, -1 pads
    k: int,
    block: int,
):
    """Score ``cand`` against its rows and merge into the top-k state — one
    jitted ``lax.map`` over ``block``-row tiles (the same blocked-scan shape
    as ``build._prune_all``; no untraced Python block loop)."""
    n = x.shape[0]
    n_pad = pad_to(n, block)
    rows = jnp.arange(n_pad, dtype=jnp.int32)
    u_pad = jnp.where(rows < n, rows, 0)
    ids_p = pad_rows(ids, n_pad, -1)
    dist_p = pad_rows(dist, n_pad, jnp.inf)
    cand_p = pad_rows(cand, n_pad, -1)

    def one_block(args):
        u, i_b, d_b, c_b = args
        xc = x[jnp.clip(c_b, 0, n - 1)]
        xu = x[u]
        db = squared_dist(xu[:, None, :], xc)[:, 0, :]
        db = jnp.where((c_b < 0) | (c_b == u[:, None]), jnp.inf, db)
        return merge_topk(i_b, d_b, c_b, db, k)

    mi, md = jax.lax.map(
        one_block,
        (
            u_pad.reshape(-1, block),
            ids_p.reshape(-1, block, ids.shape[1]),
            dist_p.reshape(-1, block, dist.shape[1]),
            cand_p.reshape(-1, block, cand.shape[1]),
        ),
    )
    return mi.reshape(n_pad, k)[:n], md.reshape(n_pad, k)[:n]


def nn_descent(
    key: jax.Array,
    x: jnp.ndarray,
    k: int,
    *,
    iters: int = 6,
    sample: int = 8,
    block: int = 4096,
) -> KnnState:
    """Fixed-width NN-descent: local join over forward, reverse and random
    candidates, merged with blocked matmul distances (``lax.map`` tiles)."""
    n, _ = x.shape
    key, k0 = jax.random.split(key)
    init_ids = jax.random.randint(k0, (n, k), 0, n, dtype=jnp.int32)

    # Initial state: sort + dedup the random seeds (merge into an empty beam).
    empty = jnp.full((n, k), -1, jnp.int32)
    ids, dist = _blocked_refine(
        x, empty, jnp.full((n, k), jnp.inf, jnp.float32), init_ids, k, block
    )

    for it in range(iters):
        key, k1 = jax.random.split(key)
        fwd = ids[:, :sample]                                   # (n, S)
        non = ids[jnp.clip(fwd, 0, n - 1), :sample].reshape(n, sample * sample)
        non = jnp.where(fwd[:, :1] < 0, -1, non)
        rev = _reverse_candidates(ids, sample)
        rnd = jax.random.randint(k1, (n, 4), 0, n, dtype=jnp.int32)
        cand = jnp.concatenate([non, rev, rnd], axis=1)
        ids, dist = _blocked_refine(x, ids, dist, cand, k, block)
    return KnnState(ids, dist)


def attribute_width(ef_attribute: int) -> int:
    """Total attribute-candidate columns: 2 sides × ``ef_attribute/8`` per
    side × 4 sort keys (Alg. 1 lines 3-10).  Owned here so consumers (e.g.
    ``bench_build``'s sweep-shape profile) cannot drift from the builder."""
    return 8 * max(ef_attribute // 8, 1)


def candidate_pool_width(ef_spatial: int, ef_attribute: int) -> int:
    """Iteration-0 candidate-pool width of :func:`generate_candidates`."""
    return ef_spatial + attribute_width(ef_attribute)


@functools.partial(jax.jit, static_argnames=("ef_attribute",))
def attribute_candidates(intervals: jnp.ndarray, ef_attribute: int) -> jnp.ndarray:
    """Alg. 1 lines 3-10: neighbors in the four interval-derived sort orders."""
    n = intervals.shape[0]
    w = attribute_width(ef_attribute) // 8    # per-side width per sort key
    l = intervals[:, 0]
    r = intervals[:, 1]
    keys = [l, r, (l + r) * 0.5, r - l]
    outs = []
    offsets = jnp.concatenate(
        [jnp.arange(-w, 0, dtype=jnp.int32), jnp.arange(1, w + 1, dtype=jnp.int32)]
    )
    for kv in keys:
        order = jnp.argsort(sort_key_i32(kv), stable=True).astype(jnp.int32)  # rank -> id
        inv = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
        pos = inv[:, None] + offsets[None, :]                         # (n, 2w)
        ok = (pos >= 0) & (pos < n)
        nb = order[jnp.clip(pos, 0, n - 1)]
        outs.append(jnp.where(ok, nb, -1))
    return jnp.concatenate(outs, axis=1)                              # (n, 8w)


def generate_candidates(
    key: jax.Array,
    x: jnp.ndarray,
    intervals: jnp.ndarray,
    *,
    ef_spatial: int,
    ef_attribute: int,
    nnd_iters: int = 6,
    exact_spatial: bool = False,
) -> jnp.ndarray:
    """Paper Algorithm 1: spatial ∪ attribute candidates, dedup'd, self-free.

    ``exact_spatial=True`` swaps NN-descent for the exact KNN oracle (small n).
    """
    if exact_spatial:
        spa = brute_force_knn(x, ef_spatial).ids
    else:
        spa = nn_descent(key, x, ef_spatial, iters=nnd_iters).ids
    attr = attribute_candidates(intervals, ef_attribute)
    cand = jnp.concatenate([spa, attr], axis=1)
    self_ids = jnp.arange(x.shape[0], dtype=jnp.int32)[:, None]
    cand = jnp.where(cand == self_ids, -1, cand)
    return cand
