"""The plain reference every answer is judged by, and the judgement.

Brute force over the run's own rows (made by :mod:`corpus`, never read
back from the program): exact predicate-filtered top-k at ``HIGHEST``
matmul precision, and the exact distance of every returned row as an
elementwise f32 sum.  The program's ids name its slots, and indexed row
``i`` sits in slot ``i``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512


@functools.partial(jax.jit, static_argnames=("k", "low"))
def _topk_block(x, ivs, q, qint, qflt, *, k, low):
    """Exact filtered top-k of one block of queries over every row.

    ``low`` computes in bfloat16: the control that must fail the check."""
    if low:
        xb, qb = x.astype(jnp.bfloat16), q.astype(jnp.bfloat16)
        xn = jnp.sum(jnp.square(xb.astype(jnp.float32)), -1)
        qn = jnp.sum(jnp.square(qb.astype(jnp.float32)), -1)
        ip = jnp.dot(qb, xb.T, preferred_element_type=jnp.float32)
        d = (qn[:, None] + xn[None, :] - 2.0 * ip).astype(jnp.bfloat16)
        d = jnp.maximum(d.astype(jnp.float32), 0.0)
    else:
        xn = jnp.sum(x * x, -1)
        qn = jnp.sum(q * q, -1)
        ip = jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)
        d = jnp.maximum(qn[:, None] + xn[None, :] - 2.0 * ip, 0.0)
    lo, hi = ivs[None, :, 0], ivs[None, :, 1]
    ql, qr = qint[:, 0:1], qint[:, 1:2]
    ok = jnp.where(qflt[:, None], (ql <= lo) & (hi <= qr), (lo <= ql) & (qr <= hi))
    neg, idx = jax.lax.top_k(-jnp.where(ok, d, jnp.inf), k)
    return jnp.where(jnp.isfinite(neg), idx, -1).astype(jnp.int32), -neg


def topk(ref_x, ref_iv, qv, qint, qflt, k, *, low=False):
    """Filtered top-k (row ids, distances) for each query, in blocks of
    :data:`BLOCK` queries so that it fits beside nothing else."""
    x, ivs = jnp.asarray(ref_x), jnp.asarray(ref_iv)
    nq = len(qv)
    ids = np.empty((nq, k), np.int32)
    dist = np.empty((nq, k), np.float32)
    for s in range(0, nq, BLOCK):
        e = min(s + BLOCK, nq)
        pad = BLOCK - (e - s)
        args = [np.concatenate([a[s:e], np.repeat(a[s:s + 1], pad, 0)])
                for a in (qv, qint, qflt)]
        i, d = _topk_block(x, ivs, *args, k=k, low=low)
        ids[s:e], dist[s:e] = np.asarray(i)[:e - s], np.asarray(d)[:e - s]
    return ids, dist


def exact_dist(ref_x, rows, qv):
    """Squared distance of each (query, row) pair as an elementwise
    difference sum in f32: no matmul identity, no cancellation."""
    diff = ref_x[np.maximum(rows, 0)] - qv[:, None, :]
    return np.sum(diff * diff, axis=-1, dtype=np.float32)


def satisfies(ref_iv, rows, qint, qflt):
    lo, hi = ref_iv[np.maximum(rows, 0), 0], ref_iv[np.maximum(rows, 0), 1]
    ql, qr = qint[:, :1], qint[:, 1:]
    return np.where(qflt[:, None], (ql <= lo) & (hi <= qr), (lo <= ql) & (qr <= hi))


def judge(ans, data, *, k, truth=None):
    """Judge answered queries.  ``ans``: dict of ``q`` (pool index), ``ids``
    and ``dist``.  Returns per-query recall, each query's semantics, and
    the compared numbers: ids that name no row, rows that fail the
    predicate, and rows repeated in one answer (``bad_rows``), and the
    widest relative gap between a reported distance and the exact one
    (``dist_gap``).  An id of -1 pads an answer with fewer than ``k``
    rows."""
    q, ids = ans["q"], ans["ids"]
    qv, qint, qflt = data["qv"][q], data["qint"][q], data["qflt"][q]
    ref_x, ref_iv = data["ref_x"], data["ref_iv"]
    if truth is None:
        truth = topk(ref_x, ref_iv, qv, qint, qflt, k)
    t_ids, _ = truth
    ret = (ids >= 0) & (ids < len(ref_x))
    ok = ret & satisfies(ref_iv, ids, qint, qflt)
    srt = np.sort(np.where(ret, ids, -1 - np.arange(k)), axis=1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    bad = int(np.sum(~ret & (ids != -1)) + np.sum(ret & ~ok) + np.sum(dup))
    exact = exact_dist(ref_x, ids, qv)
    gap = np.abs(ans["dist"] - exact) / np.maximum(exact, 1e-6)
    dist_gap = float(np.max(gap[ret], initial=0.0))
    hits = np.array([len(set(r[r >= 0].tolist()) & set(t[t >= 0].tolist()))
                     for r, t in zip(np.where(ret, ids, -1), t_ids)])
    want = np.sum(t_ids >= 0, axis=1)
    recall = np.where(want > 0, hits / np.maximum(want, 1), np.nan)
    return dict(recall=recall, sem=data["qsem"][q], bad_rows=bad,
                dist_gap=dist_gap)


def recall_by_semantics(recall, sem, names):
    out = {}
    for i, name in enumerate(names):
        r = recall[(sem == i) & ~np.isnan(recall)]
        if r.size:
            out[name] = float(r.mean())
    return out
