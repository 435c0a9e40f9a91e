"""Entry acquisition (Alg. 5 / Lemma 4.3) + beam search (Alg. 4) tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import intervals as iv
from repro.core.build import UGConfig
from repro.core.entry import build_entry_index, get_entry, get_entry_batch
from repro.core.exact import build_exact
from repro.core.index import UGIndex, recall
from repro.core.search import brute_force, search
from repro.core.store import make_store

pytestmark = pytest.mark.hermetic  # runs in the no-hypothesis CI job

unit = st.floats(0, 1, allow_nan=False, width=32)


@pytest.fixture(scope="module")
def eidx_data():
    k = jax.random.key(3)
    ints = iv.sample_uniform_intervals(k, 500)
    return ints, build_entry_index(ints)


@pytest.mark.parametrize("n", [1, 1023, 1024, 3001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_blocked_scan_matches_lax(n, dtype):
    """The entry index's two-level running min/max equals ``lax.cummin`` /
    ``lax.cummax`` exactly, both directions, on ragged lengths."""
    from repro.core.entry import _cumulative

    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.integers(-1000, 1000, size=n)).astype(dtype)
    for op, ref in (("min", jax.lax.cummin), ("max", jax.lax.cummax)):
        for rev in (False, True):
            np.testing.assert_array_equal(
                np.asarray(_cumulative(x, op, rev)),
                np.asarray(ref(x, reverse=rev)))


def test_sort_key_i32_sorts_like_f32():
    """Order-preserving int32 keys give the f32 argsort's permutation,
    with negative values, ±0, ±inf and ties."""
    from repro.kernels.util import sort_key_i32

    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(size=2000), np.round(rng.normal(size=500), 1),
                        [0.0, -0.0, np.inf, -np.inf, -0.0, 0.0]]).astype(np.float32)
    x = jnp.asarray(x[rng.permutation(x.size)])
    np.testing.assert_array_equal(
        np.asarray(jnp.argsort(sort_key_i32(x), stable=True)),
        np.asarray(jnp.argsort(x, stable=True)))


@settings(max_examples=60, deadline=None)
@given(unit, unit)
def test_entry_lemma_4_3(ql, qr):
    """Returned node satisfies the predicate; NULL implies none exists."""
    k = jax.random.key(3)
    ints = iv.sample_uniform_intervals(k, 500)
    eidx = build_entry_index(ints)
    lo, hi = min(ql, qr), max(ql, qr)
    q = jnp.asarray([lo, hi], jnp.float32)
    ints_np = np.asarray(ints)
    for sem in (iv.Semantics.IF, iv.Semantics.IS):
        e = int(get_entry(eidx, q, sem))
        if sem is iv.Semantics.IF:
            any_valid = bool(((ints_np[:, 0] >= lo) & (ints_np[:, 1] <= hi)).any())
            if e >= 0:
                assert ints_np[e, 0] >= lo and ints_np[e, 1] <= hi
            else:
                assert not any_valid
        else:
            any_valid = bool(((ints_np[:, 0] <= lo) & (ints_np[:, 1] >= hi)).any())
            if e >= 0:
                assert ints_np[e, 0] <= lo and ints_np[e, 1] >= hi
            else:
                assert not any_valid


@settings(max_examples=40, deadline=None)
@given(unit, unit)
def test_entry_batch_widened_lemma(ql, qr):
    """Widened Alg. 5: every non-NULL id in the batch is a valid entry,
    ids are distinct, and column 0 equals the single-entry result."""
    k = jax.random.key(3)
    ints = iv.sample_uniform_intervals(k, 500)
    eidx = build_entry_index(ints)
    lo, hi = min(ql, qr), max(ql, qr)
    q = jnp.asarray([lo, hi], jnp.float32)
    ints_np = np.asarray(ints)
    for sem in (iv.Semantics.IF, iv.Semantics.IS):
        batch = np.asarray(get_entry_batch(eidx, q, sem, width=6))
        assert batch.shape == (6,)
        assert int(batch[0]) == int(get_entry(eidx, q, sem))
        real = [int(v) for v in batch if v >= 0]
        assert len(real) == len(set(real))
        for e in real:
            if sem is iv.Semantics.IF:
                assert ints_np[e, 0] >= lo and ints_np[e, 1] <= hi
            else:
                assert ints_np[e, 0] <= lo and ints_np[e, 1] >= hi


def test_entry_batch_batched_queries(eidx_data):
    """Batch axis broadcasting: (B, 2) query intervals -> (B, W) ids."""
    ints, eidx = eidx_data
    q = jnp.asarray([[0.0, 1.0], [0.4, 0.6], [2.0, 3.0]], jnp.float32)
    out = get_entry_batch(eidx, q, iv.Semantics.IF, width=4)
    assert out.shape == (3, 4)
    assert int(out[0, 0]) >= 0         # whole domain: entry must exist
    assert bool((out[2] == -1).all())  # out-of-range window: certified NULL


def test_entry_masked(eidx_data):
    """node_mask excludes rows from entry consideration (sharded pad rows)."""
    ints, _ = eidx_data
    mask = jnp.arange(ints.shape[0]) < 100
    eidx = build_entry_index(ints, node_mask=mask)
    q = jnp.asarray([0.0, 1.0], jnp.float32)
    e = int(get_entry(eidx, q, iv.Semantics.IF))
    assert 0 <= e < 100


def test_search_exact_graph_full_recall(small_corpus, queries):
    """On the exact URNG, beam search recall@10 == 1.0 (Cor. 3.4 + heredity)."""
    x, ints = small_corpus
    g = build_exact(x, ints, unified=True)
    eidx = build_entry_index(ints)
    qv, qi = queries
    store = make_store(x, ints, g.nbrs, g.status, entry=eidx)
    for sem in (iv.Semantics.IF, iv.Semantics.IS):
        res = search(store, qv, qi, sem=sem, ef=48, k=10)
        gt = brute_force(x, ints, qv, qi, sem=sem, k=10)
        assert recall(res, gt) == 1.0, sem


def test_search_no_valid_nodes(small_corpus):
    """Impossible queries return all -1 (NULL entry path)."""
    x, ints = small_corpus
    g = build_exact(x, ints, unified=True)
    eidx = build_entry_index(ints)
    qv = jnp.zeros((2, x.shape[1]))
    impossible = jnp.asarray([[0.4999, 0.5001], [0.5, 0.5]], jnp.float32)
    store = make_store(x, ints, g.nbrs, g.status, entry=eidx)
    res = search(store, qv, impossible,
                 sem=iv.Semantics.IS, ef=16, k=5)
    # IS with a near-point query can have matches; use an out-of-range one
    impossible2 = jnp.asarray([[-5.0, 5.0], [-5.0, 5.0]], jnp.float32)
    res2 = search(store, qv, impossible2,
                  sem=iv.Semantics.IS, ef=16, k=5)
    assert bool((res2.ids == -1).all())


@pytest.mark.slow
def test_search_results_satisfy_predicate(medium_corpus):
    """Every returned id satisfies the query predicate (search never leaves
    the valid subgraph — Alg. 4 lines 11-20)."""
    x, ints = medium_corpus
    cfg = UGConfig(ef_spatial=24, ef_attribute=48, max_edges_if=24, max_edges_is=24,
                   iterations=2, repair_width=8, exact_spatial=True, block=768)
    idx = UGIndex.build(x, ints, cfg)
    k1, k2 = jax.random.split(jax.random.key(9))
    qv = jax.random.normal(k1, (24, x.shape[1]))
    c = jax.random.uniform(k2, (24, 1))
    qi = jnp.concatenate([jnp.maximum(c - 0.3, 0), jnp.minimum(c + 0.3, 1)], axis=1)
    ints_np = np.asarray(ints)
    for sem in (iv.Semantics.IF, iv.Semantics.IS):
        res = idx.search(qv, qi, sem=sem, ef=48, k=10)
        ids = np.asarray(res.ids)
        qn = np.asarray(qi)
        for i in range(ids.shape[0]):
            for v in ids[i]:
                if v < 0:
                    continue
                if sem is iv.Semantics.IF:
                    assert qn[i, 0] <= ints_np[v, 0] and ints_np[v, 1] <= qn[i, 1]
                else:
                    assert ints_np[v, 0] <= qn[i, 0] and qn[i, 1] <= ints_np[v, 1]


@pytest.mark.slow
def test_ug_recall_threshold(medium_corpus):
    """Practical UG achieves high recall on all four semantics (Exp-1/2)."""
    x, ints = medium_corpus
    cfg = UGConfig(ef_spatial=32, ef_attribute=64, max_edges_if=32, max_edges_is=32,
                   iterations=3, repair_width=16, exact_spatial=True, block=768)
    idx = UGIndex.build(x, ints, cfg)
    k1, k2 = jax.random.split(jax.random.key(11))
    nq = 32
    qv = jax.random.normal(k1, (nq, x.shape[1]))
    c = jax.random.uniform(k2, (nq, 1))
    qi = jnp.concatenate([jnp.maximum(c - 0.3, 0), jnp.minimum(c + 0.3, 1)], axis=1)
    point = jnp.concatenate([c, c], axis=1)
    for sem, q in [
        (iv.Semantics.IF, qi), (iv.Semantics.IS, qi), (iv.Semantics.RS, point),
    ]:
        res = idx.search(qv, q, sem=sem, ef=96, k=10)
        gt = idx.ground_truth(qv, q, sem=sem, k=10)
        r = recall(res, gt)
        assert r >= 0.85, f"{sem}: recall {r}"


def test_degree_budgets(medium_corpus):
    """Per-semantic out-degree never exceeds max_edges (Alg. 3 lines 18-21)."""
    x, ints = medium_corpus
    cfg = UGConfig(ef_spatial=24, ef_attribute=48, max_edges_if=12, max_edges_is=9,
                   iterations=2, repair_width=8, exact_spatial=True, block=768)
    idx = UGIndex.build(x, ints, cfg)
    assert int(idx.graph.degree(iv.FLAG_IF).max()) <= 12
    assert int(idx.graph.degree(iv.FLAG_IS).max()) <= 9


def test_save_load_roundtrip(tmp_path, medium_corpus):
    x, ints = medium_corpus
    cfg = UGConfig(ef_spatial=16, ef_attribute=32, max_edges_if=16, max_edges_is=16,
                   iterations=1, exact_spatial=True, block=768)
    idx = UGIndex.build(x, ints, cfg)
    idx.save(tmp_path / "idx")
    idx2 = UGIndex.load(tmp_path / "idx")
    assert bool(jnp.array_equal(idx.graph.nbrs, idx2.graph.nbrs))
    assert bool(jnp.array_equal(idx.graph.status, idx2.graph.status))
    assert idx2.config.max_edges_if == 16
