"""The jitted build paths equal the op-by-op code they replaced, bit for bit.

Each reference below is the earlier implementation, kept here verbatim in
spirit: the exact KNN candidates as nested Python loops over query and
corpus blocks, the attribute candidates and ``segment_scatter`` run
eagerly, and the entry index's arg-scans as a pairwise
``lax.associative_scan``.  The program under test replaced each with one
compiled program; these tests pin that nothing but the dispatch changed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import candidates as cand_mod
from repro.core.entry import _argscan, build_entry_index
from repro.core.prune import squared_dist
from repro.kernels.util import segment_scatter

pytestmark = pytest.mark.hermetic


def _loop_knn(x, k, block, x_block):
    """The former exact KNN: per query block, stream corpus blocks through
    top-k + ``merge_topk``, then drop self and keep the k closest."""
    n = x.shape[0]
    ids_all, d_all = [], []
    for s in range(0, n, block):
        q = x[s:s + block]
        nq = q.shape[0]
        ids = jnp.full((nq, k + 1), -1, jnp.int32)
        d = jnp.full((nq, k + 1), jnp.inf, jnp.float32)
        for t in range(0, n, x_block):
            xb = x[t:t + x_block]
            db = squared_dist(q, xb)
            bids = jnp.broadcast_to(
                jnp.arange(t, t + xb.shape[0], dtype=jnp.int32), db.shape)
            neg, idx = jax.lax.top_k(-db, min(k + 1, xb.shape[0]))
            ids, d = cand_mod.merge_topk(
                ids, d, jnp.take_along_axis(bids, idx, axis=-1), -neg, k + 1)
        self_ids = jnp.arange(s, s + nq, dtype=jnp.int32)[:, None]
        d = jnp.where(ids == self_ids, jnp.inf, d)
        order = jnp.argsort(d, axis=-1)[:, :k]
        ids_all.append(jnp.take_along_axis(ids, order, axis=-1))
        d_all.append(jnp.take_along_axis(d, order, axis=-1))
    return np.concatenate(ids_all), np.concatenate(d_all)


@pytest.mark.parametrize("n,k,block,x_block", [
    (300, 8, 128, 64),      # ragged query tiles and corpus chunks
    (1000, 16, 256, 512),
    (257, 4, 2048, 4096),   # one tile, one chunk
])
def test_exact_knn_scan_matches_loops(n, k, block, x_block):
    rng = np.random.default_rng(n)
    x = rng.normal(size=(n, 12)).astype(np.float32)
    x[7] = x[3]                                   # a zero-distance pair
    x[50:60] = np.round(x[50:60])                 # tied distances
    x = jnp.asarray(x)
    got = cand_mod.brute_force_knn(x, k, block=block, x_block=x_block)
    ref_ids, ref_d = _loop_knn(x, k, block, x_block)
    np.testing.assert_array_equal(np.asarray(got.ids), ref_ids)
    np.testing.assert_array_equal(np.asarray(got.dist), ref_d)


def test_exact_knn_valid_mask_drops_rows():
    """Rows outside ``valid`` never appear as candidates; the rest is the
    KNN of the valid rows alone (the shard pad rows of the sharded build)."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(200, 8)).astype(np.float32))
    valid = jnp.arange(200) < 150
    got = cand_mod.brute_force_knn(x, 6, block=64, x_block=32, valid=valid)
    sub = cand_mod.brute_force_knn(x[:150], 6, block=64, x_block=32)
    np.testing.assert_array_equal(np.asarray(got.ids[:150]), np.asarray(sub.ids))
    assert not np.isin(np.asarray(got.ids), np.arange(150, 200)).any()


def _eager_attribute_candidates(intervals, ef_attribute):
    """The former un-jitted Alg. 1 sort orders (f32 argsort keys)."""
    n = intervals.shape[0]
    w = cand_mod.attribute_width(ef_attribute) // 8
    l, r = intervals[:, 0], intervals[:, 1]
    offsets = jnp.concatenate([jnp.arange(-w, 0, dtype=jnp.int32),
                               jnp.arange(1, w + 1, dtype=jnp.int32)])
    outs = []
    for kv in (l, r, (l + r) * 0.5, r - l):
        order = jnp.argsort(kv, stable=True).astype(jnp.int32)
        inv = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
        pos = inv[:, None] + offsets[None, :]
        ok = (pos >= 0) & (pos < n)
        outs.append(jnp.where(ok, order[jnp.clip(pos, 0, n - 1)], -1))
    return np.asarray(jnp.concatenate(outs, axis=1))


@pytest.mark.parametrize("ef_attribute", [8, 64])
def test_attribute_candidates_match_eager(ef_attribute):
    rng = np.random.default_rng(ef_attribute)
    pts = np.round(rng.uniform(size=(777, 2)), 2)  # many tied keys
    pts[:5] = 0.0                                   # zero-length, zero keys
    ints = jnp.asarray(np.sort(pts, axis=1).astype(np.float32))
    with jax.disable_jit():
        ref = _eager_attribute_candidates(ints, ef_attribute)
    got = np.asarray(cand_mod.attribute_candidates(ints, ef_attribute))
    np.testing.assert_array_equal(got, ref)


def test_segment_scatter_jit_matches_eager():
    rng = np.random.default_rng(2)
    seg = jnp.asarray(rng.integers(-2, 40, size=3000).astype(np.int32))
    val = jnp.asarray(rng.integers(-1, 500, size=3000).astype(np.int32))
    with jax.disable_jit():
        ref = np.asarray(segment_scatter(seg, val, 37, 6))
    np.testing.assert_array_equal(np.asarray(segment_scatter(seg, val, 37, 6)), ref)


def _pairwise_argscan(vals, ids, op, reverse):
    """The former entry-index scan: ``associative_scan`` over (value, id)."""
    def combine(a, b):
        take_b = (b[0] < a[0]) if op == "min" else (b[0] > a[0])
        return jnp.where(take_b, b[0], a[0]), jnp.where(take_b, b[1], a[1])

    return jax.lax.associative_scan(combine, (vals, ids), reverse=reverse)


@pytest.mark.parametrize("n", [1, 5, 1024, 2500])
def test_argscan_matches_associative_scan(n):
    rng = np.random.default_rng(n)
    vals = np.round(rng.uniform(size=n), 1).astype(np.float32)  # ties
    vals[rng.uniform(size=n) < 0.1] = np.inf
    vals, ids = jnp.asarray(vals), jnp.asarray(rng.permutation(n).astype(np.int32))
    for op, rev in (("min", True), ("max", False)):
        v = vals if op == "min" else jnp.where(jnp.isinf(vals), -jnp.inf, vals)
        got = _argscan(v, ids, op, rev)
        ref = _pairwise_argscan(v, ids, op, rev)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_entry_index_masked_rows_match_associative_scan():
    """The whole entry index (masked rows included) against the pairwise
    scans over the same sorted arrays."""
    rng = np.random.default_rng(9)
    pts = np.sort(np.round(rng.uniform(size=(900, 2)), 2), axis=1)
    ints = jnp.asarray(pts.astype(np.float32))
    mask = jnp.asarray(rng.uniform(size=900) < 0.8)
    e = build_entry_index(ints, node_mask=mask)
    l = jnp.where(mask, ints[:, 0], jnp.inf)
    order = jnp.argsort(l, stable=True).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(e.node_id), np.asarray(order))
    rmin = jnp.where(mask, ints[:, 1], jnp.inf)[order]
    rmax = jnp.where(mask, ints[:, 1], -jnp.inf)[order]
    sv, si = _pairwise_argscan(rmin, order, "min", True)
    pv, pi = _pairwise_argscan(rmax, order, "max", False)
    for got, ref in ((e.suffmin_r_val, sv), (e.suffmin_r_id, si),
                     (e.prefmax_r_val, pv), (e.prefmax_r_id, pi)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
