"""Entry-node acquisition (paper Alg. 5, Lemma 4.3).

Nodes are sorted by interval left endpoint; two auxiliary arrays — the suffix
minimum and prefix maximum of right endpoints (with arg-indices) — let a valid
entry node be found in O(log n) for both IF and IS queries, or NULL certified
when no valid node exists.

Built from cumulative min/max scans so the structure is jittable and can
be constructed per shard inside ``shard_map`` (each index shard owns its own
entry arrays; see DESIGN.md §4).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import intervals as iv
from repro.kernels.util import sort_key_i32


class EntryIndex(NamedTuple):
    node_id: jnp.ndarray        # (n,) int32 — node ids sorted by left endpoint
    l_sorted: jnp.ndarray       # (n,) f32   — sorted left endpoints
    suffmin_r_val: jnp.ndarray  # (n,) f32   — min right endpoint over suffix
    suffmin_r_id: jnp.ndarray   # (n,) int32 — arg node id of that minimum
    prefmax_r_val: jnp.ndarray  # (n,) f32   — max right endpoint over prefix
    prefmax_r_id: jnp.ndarray   # (n,) int32 — arg node id of that maximum


_SCAN_BLOCK = 1024


def _cumulative(x: jnp.ndarray, op: str, reverse: bool) -> jnp.ndarray:
    """``lax.cummin``/``cummax`` of a 1-D array as a two-level scan: along
    ``_SCAN_BLOCK``-wide rows, then across the rows' totals.  Exact (min and
    max do not round), and at a million rows a TPU compiles it far faster
    than the 1-D scan of f32 values (PERF.md has the times)."""
    n = x.shape[0]
    big = jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).max
    fill = jnp.asarray(big if op == "min" else -big, x.dtype)
    scan = jax.lax.cummin if op == "min" else jax.lax.cummax
    comb = jnp.minimum if op == "min" else jnp.maximum
    rows = -(-n // _SCAN_BLOCK)
    xb = jnp.concatenate([x, jnp.full((rows * _SCAN_BLOCK - n,), fill, x.dtype)])
    inner = scan(xb.reshape(rows, _SCAN_BLOCK), axis=1, reverse=reverse)
    total = scan(inner[:, 0] if reverse else inner[:, -1], reverse=reverse)
    # what the rows scanned before each row carry into it
    carry = (jnp.concatenate([total[1:], fill[None]]) if reverse
             else jnp.concatenate([fill[None], total[:-1]]))
    return comb(inner, carry[:, None]).reshape(-1)[:n]


def _argscan(vals: jnp.ndarray, ids: jnp.ndarray, op: str, reverse: bool):
    """Running min (``reverse``: over each suffix) or max (over each
    prefix) of ``vals``, with the id of the element that holds it; on ties
    the element met first in scan order keeps it.

    Built from cumulative min/max only: the value scan, then for each
    position the nearest scan-order position where a strictly better value
    starts.  (A pairwise ``associative_scan`` gives the same answer but
    compiles in minutes at a million rows on a TPU.)"""
    n = vals.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    best = _cumulative(vals, op, reverse)
    # best over the positions scanned before this one (sentinel at the start)
    sentinel = jnp.full((1,), jnp.inf if op == "min" else -jnp.inf, vals.dtype)
    prev = (jnp.concatenate([best[1:], sentinel]) if reverse
            else jnp.concatenate([sentinel, best[:-1]]))
    starts = (vals < prev) if op == "min" else (vals > prev)
    starts = starts.at[n - 1 if reverse else 0].set(True)
    if reverse:
        at = _cumulative(jnp.where(starts, pos, n), "min", True)
    else:
        at = _cumulative(jnp.where(starts, pos, -1), "max", False)
    return best, ids[at]


@jax.jit
def build_entry_index(
    intervals: jnp.ndarray, node_mask: jnp.ndarray | None = None
) -> EntryIndex:
    """Sort by left endpoint and precompute suffix-min / prefix-max of rights.

    ``node_mask`` excludes nodes (masked rows get ``l=+inf`` so they sort last
    and sentinel rights so they never win a scan) — used for per-shard or
    filtered sub-index entry structures.
    """
    n = intervals.shape[0]
    l = intervals[:, 0].astype(jnp.float32)
    r = intervals[:, 1].astype(jnp.float32)
    if node_mask is not None:
        l = jnp.where(node_mask, l, jnp.inf)
        r_for_min = jnp.where(node_mask, r, jnp.inf)
        r_for_max = jnp.where(node_mask, r, -jnp.inf)
    else:
        r_for_min = r
        r_for_max = r
    order = jnp.argsort(sort_key_i32(l), stable=True).astype(jnp.int32)
    l_s = l[order]
    rmin_s = r_for_min[order]
    rmax_s = r_for_max[order]
    sv, si = _argscan(rmin_s, order, "min", reverse=True)
    pv, pi = _argscan(rmax_s, order, "max", reverse=False)
    return EntryIndex(order, l_s, sv, si, pv, pi)


def _entry_if(eidx: EntryIndex, ql: jnp.ndarray, qr: jnp.ndarray) -> jnp.ndarray:
    """IF/RF branch of Alg. 5: first position with ``l ≥ q.l``, suffix-min
    right endpoint certifies a valid node or NULL (Lemma 4.3)."""
    n = eidx.l_sorted.shape[0]
    i = jnp.searchsorted(eidx.l_sorted, ql, side="left")
    ok = i < n
    ic = jnp.clip(i, 0, n - 1)
    ok = ok & (eidx.suffmin_r_val[ic] <= qr)
    return jnp.where(ok, eidx.suffmin_r_id[ic], -1).astype(jnp.int32)


def _entry_is(eidx: EntryIndex, ql: jnp.ndarray, qr: jnp.ndarray) -> jnp.ndarray:
    """IS/RS branch of Alg. 5 (dual: prefix-max over ``l ≤ q.l``)."""
    n = eidx.l_sorted.shape[0]
    i = jnp.searchsorted(eidx.l_sorted, ql, side="right") - 1
    ok = i >= 0
    ic = jnp.clip(i, 0, n - 1)
    ok = ok & (eidx.prefmax_r_val[ic] >= qr)
    return jnp.where(ok, eidx.prefmax_r_id[ic], -1).astype(jnp.int32)


def get_entry_flags(
    eidx: EntryIndex, q_interval: jnp.ndarray, sem_flags: jnp.ndarray
) -> jnp.ndarray:
    """Alg. 5 with runtime per-query semantics: ``sem_flags`` (…,) int32
    selects the IF or IS branch per query, so one compiled program serves a
    mixed batch.  Each selected lane is computed exactly as the static path
    computes it (bitwise-equal results)."""
    ql = q_interval[..., 0]
    qr = q_interval[..., 1]
    return jnp.where(
        iv.is_filter_flag(sem_flags), _entry_if(eidx, ql, qr), _entry_is(eidx, ql, qr)
    ).astype(jnp.int32)


def get_entry(
    eidx: EntryIndex, q_interval: jnp.ndarray, sem: iv.Semantics
) -> jnp.ndarray:
    """Alg. 5 for a batch of query intervals (..., 2) -> (...,) int32 ids.

    Returns -1 when no valid node exists (the NULL case of Lemma 4.3).
    RF == IF and RS == IS after degenerate-interval reduction (§2.1).
    """
    ql = q_interval[..., 0]
    qr = q_interval[..., 1]
    if sem in (iv.Semantics.IF, iv.Semantics.RF):
        return _entry_if(eidx, ql, qr)
    return _entry_is(eidx, ql, qr)


def get_entry_batch(
    eidx: EntryIndex, q_interval: jnp.ndarray, sem: iv.Semantics, width: int = 1
) -> jnp.ndarray:
    """Widened Alg. 5: up to ``width`` *distinct* valid entries per query.

    The multi-expansion search (DESIGN.md §8) seeds its initial frontier with
    several entry nodes so the first fused step already expands ``W`` nodes.
    Lemma 4.3 generalizes position-wise: for an IF query, *every* position
    ``p ≥ i`` of the left-endpoint order whose suffix-min right endpoint is
    ``≤ q.r`` certifies a valid entry (that arg node has ``l ≥ l_sorted[p] ≥
    q.l``); dually for IS with the prefix-max over ``p ≤ i``.  Adjacent
    positions often share an arg node, so duplicates are masked to ``-1``
    (first occurrence kept).  Column 0 equals :func:`get_entry` exactly.

    Returns (..., width) int32, ``-1``-padded.
    """
    if sem in (iv.Semantics.IF, iv.Semantics.RF):
        ids = _entry_batch_if(eidx, q_interval, max(int(width), 1))
    else:
        ids = _entry_batch_is(eidx, q_interval, max(int(width), 1))
    return _mask_duplicate_entries(ids)


def get_entry_batch_flags(
    eidx: EntryIndex, q_interval: jnp.ndarray, sem_flags: jnp.ndarray, width: int = 1
) -> jnp.ndarray:
    """Widened Alg. 5 with runtime per-query semantics ((…,) int32 flags).

    Computes both branch position walks and selects per query, then masks
    duplicates exactly as :func:`get_entry_batch` — a uniform-flag batch is
    bitwise equal to the static call, so the mixed-workload search path can
    share one compiled entry program (DESIGN.md §10).
    """
    width = max(int(width), 1)
    ids = jnp.where(
        iv.is_filter_flag(sem_flags)[..., None],
        _entry_batch_if(eidx, q_interval, width),
        _entry_batch_is(eidx, q_interval, width),
    )
    return _mask_duplicate_entries(ids)


def _entry_batch_if(eidx: EntryIndex, q_interval: jnp.ndarray, width: int) -> jnp.ndarray:
    n = eidx.l_sorted.shape[0]
    ql = q_interval[..., 0]
    qr = q_interval[..., 1]
    offs = jnp.arange(width, dtype=jnp.int32)
    i = jnp.searchsorted(eidx.l_sorted, ql, side="left")
    pos = i[..., None] + offs
    ok = pos < n
    pc = jnp.clip(pos, 0, n - 1)
    ok = ok & (eidx.suffmin_r_val[pc] <= qr[..., None])
    return jnp.where(ok, eidx.suffmin_r_id[pc], -1)


def _entry_batch_is(eidx: EntryIndex, q_interval: jnp.ndarray, width: int) -> jnp.ndarray:
    n = eidx.l_sorted.shape[0]
    ql = q_interval[..., 0]
    qr = q_interval[..., 1]
    offs = jnp.arange(width, dtype=jnp.int32)
    i = jnp.searchsorted(eidx.l_sorted, ql, side="right") - 1
    pos = i[..., None] - offs
    ok = pos >= 0
    pc = jnp.clip(pos, 0, n - 1)
    ok = ok & (eidx.prefmax_r_val[pc] >= qr[..., None])
    return jnp.where(ok, eidx.prefmax_r_id[pc], -1)


def _mask_duplicate_entries(ids: jnp.ndarray) -> jnp.ndarray:
    """Mask repeated arg nodes to -1, first occurrence kept (width is small,
    so the O(width²) pairwise mask is fine here)."""
    width = ids.shape[-1]
    offs = jnp.arange(width, dtype=jnp.int32)
    dup = (ids[..., :, None] == ids[..., None, :]) & (ids[..., None, :] >= 0)
    earlier = offs[:, None] > offs[None, :]
    return jnp.where(jnp.any(dup & earlier, axis=-1), -1, ids).astype(jnp.int32)
