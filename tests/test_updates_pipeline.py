"""Streaming-update subsystem tests (DESIGN.md §11) + pipeline parallelism.

The acceptance contract of the update pipeline (ISSUE 4): after 10% delete
+ 10% insert churn on a synthetic build, recall@10 for all four semantics
stays within 0.02 of a from-scratch rebuild over the same live corpus; the
traced insert/delete/repair programs materialize no quadratic
intermediate; tombstoned nodes route but never surface; slots are reused
after delete→repair; and a mutated index survives both npz and ckpt-store
round trips with bitwise-identical search results.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Semantics, UGConfig, UGIndex, recall
from repro.core import intervals as iv
from repro.core.entry import get_entry_batch_flags
from repro.core.updates import insert, update_memory_profile

CHURN_CFG = UGConfig(ef_spatial=24, ef_attribute=48, max_edges_if=24,
                     max_edges_is=24, iterations=2, repair_width=8,
                     exact_spatial=True, block=512)
SMALL_CFG = UGConfig(ef_spatial=16, ef_attribute=32, max_edges_if=12,
                     max_edges_is=12, iterations=2, repair_width=8,
                     exact_spatial=True, block=256)


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def churn_data():
    """Corpus (800 base + 80 churn rows), deletion set, query workload."""
    k1, k2, k3, k4 = jax.random.split(jax.random.key(11), 4)
    n, extra, d = 800, 80, 12
    x_all = jax.random.normal(k1, (n + extra, d))
    iv_all = iv.sample_uniform_intervals(k2, n + extra)
    dels = jnp.asarray(
        np.random.default_rng(11).choice(n, size=extra, replace=False)
        .astype(np.int32)
    )
    qv = jax.random.normal(k3, (32, d))
    c = jax.random.uniform(k4, (32, 1))
    wide = jnp.concatenate(
        [jnp.maximum(c - 0.3, 0), jnp.minimum(c + 0.3, 1)], axis=1)
    point = jnp.concatenate([c, c], axis=1)
    return dict(n=n, extra=extra, x=x_all, iv=iv_all, dels=dels,
                qv=qv, wide=wide, point=point)


@pytest.fixture(scope="module")
def base_index(churn_data):
    n = churn_data["n"]
    return UGIndex.build(churn_data["x"][:n], churn_data["iv"][:n], CHURN_CFG)


@pytest.fixture(scope="module")
def deleted_index(base_index, churn_data):
    """10% delete: tombstone + iterative repair (slots become reusable)."""
    return base_index.delete(churn_data["dels"])


@pytest.fixture(scope="module")
def mutated_index(deleted_index, churn_data):
    """… then 10% insert; the batch reuses the repaired slots."""
    n = churn_data["n"]
    return deleted_index.insert(churn_data["x"][n:], churn_data["iv"][n:])


@pytest.fixture(scope="module")
def small_index():
    k1, k2 = jax.random.split(jax.random.key(5))
    n, d = 300, 10
    x = jax.random.normal(k1, (n, d))
    ints = iv.sample_uniform_intervals(k2, n)
    return UGIndex.build(x, ints, SMALL_CFG)


def _sem_cases(data):
    return [
        (Semantics.IF, data["wide"]), (Semantics.IS, data["wide"]),
        (Semantics.RS, data["point"]), (Semantics.RF, data["wide"]),
    ]


# ----------------------------------------------------- churn acceptance
def test_churn_recall_within_fresh_rebuild(mutated_index, churn_data):
    """ISSUE-4 acceptance: 10% delete + 10% insert churn stays within 0.02
    recall@10 of a from-scratch rebuild, for every semantics."""
    n = churn_data["n"]
    keep = np.setdiff1d(np.arange(n), np.asarray(churn_data["dels"]))
    x_f = jnp.concatenate([churn_data["x"][jnp.asarray(keep)],
                           churn_data["x"][n:]])
    iv_f = jnp.concatenate([churn_data["iv"][jnp.asarray(keep)],
                            churn_data["iv"][n:]])
    fresh = UGIndex.build(x_f, iv_f, CHURN_CFG)
    qv = churn_data["qv"]
    for sem, q in _sem_cases(churn_data):
        r_mut = recall(
            mutated_index.search(qv, q, sem=sem, ef=96, k=10),
            mutated_index.ground_truth(qv, q, sem=sem, k=10),
        )
        r_fresh = recall(
            fresh.search(qv, q, sem=sem, ef=96, k=10),
            fresh.ground_truth(qv, q, sem=sem, k=10),
        )
        assert r_mut >= r_fresh - 0.02, (
            f"{sem}: churned {r_mut:.3f} vs fresh rebuild {r_fresh:.3f}")


def test_churn_never_surfaces_deleted(deleted_index, mutated_index, churn_data):
    """Deleted nodes never surface; after the insert reuses their slots,
    every surfaced id is a live (reinserted or original) node."""
    dels = np.asarray(churn_data["dels"])
    for sem, q in _sem_cases(churn_data):
        res = deleted_index.search(churn_data["qv"], q, sem=sem, ef=96, k=10)
        ids = np.asarray(res.ids)
        assert not np.isin(ids[ids >= 0], dels).any(), sem
        res_m = mutated_index.search(churn_data["qv"], q, sem=sem, ef=96, k=10)
        ids_m = np.asarray(res_m.ids)
        alive = np.asarray(mutated_index.alive)
        assert alive[ids_m[ids_m >= 0]].all(), sem


def test_update_memory_profile():
    """Insert/delete/repair trace no (·,C,C) witness/dedup tensor and no
    (B,C,d) search/bridge gather; the pre-fusion legacy path shows both."""
    for backend in ("xla", "pallas"):
        prof = update_memory_profile(backend)
        assert not prof["quadratic_cc"], backend
        assert not prof["gather_bcd"], backend
    legacy = update_memory_profile("legacy")
    assert legacy["quadratic_cc"] and legacy["gather_bcd"]


# ------------------------------------------------------------ insert path
def test_incremental_insert(base_index, churn_data):
    """Inserted objects are findable; old recall is preserved; the PR-1
    ``insert`` wrapper still drives the batched pipeline."""
    n, extra = churn_data["n"], churn_data["extra"]
    idx = base_index
    idx2 = insert(idx, churn_data["x"][n:], churn_data["iv"][n:])
    assert idx2.n == n + extra
    assert idx2.capacity >= n + extra           # capacity-doubling allocator

    qv, qi = churn_data["qv"], churn_data["wide"]
    for sem in (Semantics.IF, Semantics.IS):
        # invariant: insertion preserves the pre-insert index's recall
        # (absolute recall at these small build params is corpus-dependent)
        r_before = recall(
            idx.search(qv, qi, sem=sem, ef=96, k=10),
            idx.ground_truth(qv, qi, sem=sem, k=10),
        )
        r = recall(
            idx2.search(qv, qi, sem=sem, ef=96, k=10),
            idx2.ground_truth(qv, qi, sem=sem, k=10),
        )
        assert r >= r_before - 0.05, f"{sem}: {r} vs pre-insert {r_before}"
    # degree budgets preserved after reverse-edge offers
    assert int(idx2.graph.degree(iv.FLAG_IF).max()) <= 24
    assert int(idx2.graph.degree(iv.FLAG_IS).max()) <= 24
    # an impossible-before query reaching ONLY new nodes
    new_hit = idx2.search(
        churn_data["x"][n:n + 1], jnp.asarray([[0.0, 1.0]]),
        sem=Semantics.IF, ef=64, k=1,
    )
    assert int(new_hit.ids[0, 0]) >= 0


def test_delete_then_reinsert_reuses_slot(small_index):
    """delete(repair=True) detaches the slot; the next insert reuses it
    (same physical slot id, new payload, old payload gone)."""
    idx = small_index
    victim = 17
    idx_d = idx.delete(jnp.asarray([victim]))
    assert idx_d.n == idx.n - 1
    assert bool(idx_d.free[victim]) and not bool(idx_d.alive[victim])
    new_v = jnp.ones((1, idx.x.shape[1])) * 0.25
    new_iv = jnp.asarray([[0.2, 0.8]])
    idx_r = idx_d.insert(new_v, new_iv)
    assert idx_r.capacity == idx.capacity      # no growth: slot reused
    assert bool(idx_r.alive[victim])
    assert np.allclose(np.asarray(idx_r.x[victim]), 0.25)
    hit = idx_r.search(new_v, jnp.asarray([[0.0, 1.0]]),
                       sem=Semantics.IF, ef=48, k=1)
    assert int(hit.ids[0, 0]) == victim


@pytest.mark.parametrize("frac", [0.01, 0.2])
def test_deleted_in_sets_match_full_scatter(frac):
    """In-neighbor lists scattered from the rows that point at a deleted
    node equal the scatter over the whole edge list, bit for bit."""
    from repro.core.build import scatter_repairs
    from repro.core.updates import _pad_rows_1d, deleted_in_sets

    rng = np.random.default_rng(int(frac * 100))
    cap, M = 600, 12
    nbrs = rng.integers(-1, cap, size=(cap, M)).astype(np.int32)
    dead = rng.uniform(size=cap) < frac
    to_del = (nbrs >= 0) & dead[np.clip(nbrs, 0, cap - 1)]
    src = np.broadcast_to(np.arange(cap, dtype=np.int32)[:, None], (cap, M))
    full = scatter_repairs(jnp.asarray(np.where(to_del, nbrs, -1).reshape(-1)),
                           jnp.asarray(np.where(to_del, src, -1).reshape(-1)),
                           cap, M)
    rows = _pad_rows_1d(np.flatnonzero(to_del.any(axis=1)), 64)
    got = deleted_in_sets(jnp.asarray(nbrs), jnp.asarray(to_del), rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))


@pytest.mark.parametrize("frac", [0.02, 0.3])
def test_repair_bridge_bound_is_exact(small_index, monkeypatch, frac):
    """Bounding the repair bridge by the worst row's deleted-neighbor count
    (``D``) gives the same graph, bit for bit, as bridging through all
    ``M`` neighbor slots — light deletes (small ``D``) and heavy ones."""
    import repro.core.updates as updates

    idx = small_index
    dels = np.random.default_rng(5).choice(
        idx.n, size=max(int(frac * idx.n), 1), replace=False).astype(np.int32)
    bounded = idx.delete(jnp.asarray(dels), repair_iters=2)
    monkeypatch.setattr(updates, "next_pow2", lambda v: 1 << 30)   # D = M
    full = idx.delete(jnp.asarray(dels), repair_iters=2)
    np.testing.assert_array_equal(np.asarray(bounded.store.nbrs),
                                  np.asarray(full.store.nbrs))
    np.testing.assert_array_equal(np.asarray(bounded.store.status),
                                  np.asarray(full.store.status))


def test_delete_entire_interval_band(small_index):
    """Deleting every node valid under a window makes the window's IF
    queries NULL-certify (entry -1, all rows -1) — Lemma 4.3 with the
    tombstone-masked entry structure."""
    idx = small_index
    band = jnp.asarray([0.3, 0.7], jnp.float32)
    in_band = iv.contains(band[None, :], idx.intervals)
    dels = jnp.asarray(np.flatnonzero(np.asarray(in_band)).astype(np.int32))
    assert dels.size > 0
    idx_d = idx.delete(dels)
    q = jnp.asarray([[0.3, 0.7]], jnp.float32)
    qv = jnp.zeros((1, idx.x.shape[1]))
    res = idx_d.search(qv, q, sem=Semantics.IF, ef=48, k=10)
    assert int((np.asarray(res.ids) >= 0).sum()) == 0
    gt = idx_d.ground_truth(qv, q, sem=Semantics.IF, k=10)
    assert int((np.asarray(gt.ids) >= 0).sum()) == 0


def test_tombstoned_entry_points(small_index):
    """Alg. 5 over the rebuilt entry structure never certifies a tombstone,
    and surviving certificates stay valid (get_entry_batch_flags)."""
    idx = small_index
    nq = 24
    k1, k2 = jax.random.split(jax.random.key(9))
    c = jax.random.uniform(k1, (nq, 1))
    qints = jnp.concatenate(
        [jnp.maximum(c - 0.25, 0), jnp.minimum(c + 0.25, 1)], axis=1)
    flags = iv.as_sem_flags(
        [Semantics.IF, Semantics.IS] * (nq // 2), nq)
    ent0 = np.asarray(get_entry_batch_flags(idx.entry, qints, flags, width=4))
    victims = np.unique(ent0[ent0 >= 0])[:5].astype(np.int32)
    idx_d = idx.delete(jnp.asarray(victims), repair=False)
    ent1 = np.asarray(
        get_entry_batch_flags(idx_d.entry, qints, flags, width=4))
    assert not np.isin(ent1[ent1 >= 0], victims).any()
    # every certificate is genuinely valid for its query (Lemma 4.3)
    ivs = np.asarray(idx.intervals)
    qn = np.asarray(qints)
    fl = np.asarray(flags)
    for i in range(nq):
        for e in ent1[i]:
            if e < 0:
                continue
            if fl[i] == iv.FLAG_IF:
                assert qn[i, 0] <= ivs[e, 0] and ivs[e, 1] <= qn[i, 1]
            else:
                assert ivs[e, 0] <= qn[i, 0] and qn[i, 1] <= ivs[e, 1]


def test_tombstone_routes_but_never_surfaces(small_index):
    """repair=False leaves tombstones in the graph: search still reaches
    everything live (routing through dead nodes), but never returns one."""
    idx = small_index
    rng = np.random.default_rng(3)
    dels = jnp.asarray(rng.choice(idx.n, size=30, replace=False)
                       .astype(np.int32))
    idx_d = idx.delete(dels, repair=False)
    # tombstoned rows keep their edges (routing preserved) …
    assert int(jnp.sum(idx_d.graph.nbrs[dels] >= 0)) > 0
    # … and their slots are not yet reusable
    assert not bool(jnp.any(idx_d.free))
    k1, k2 = jax.random.split(jax.random.key(13))
    qv = jax.random.normal(k1, (16, idx.x.shape[1]))
    c = jax.random.uniform(k2, (16, 1))
    qi = jnp.concatenate(
        [jnp.maximum(c - 0.3, 0), jnp.minimum(c + 0.3, 1)], axis=1)
    for sem in (Semantics.IF, Semantics.IS):
        res = idx_d.search(qv, qi, sem=sem, ef=64, k=10)
        ids = np.asarray(res.ids)
        assert not np.isin(ids[ids >= 0], np.asarray(dels)).any()
        r = recall(res, idx_d.ground_truth(qv, qi, sem=sem, k=10))
        r0 = recall(idx.search(qv, qi, sem=sem, ef=64, k=10),
                    idx.ground_truth(qv, qi, sem=sem, k=10))
        assert r >= r0 - 0.1, f"{sem}: tombstoned {r} vs static {r0}"
    # a later repair detaches them and frees the slots
    from repro.core.updates import repair_deleted

    idx_r = repair_deleted(idx_d)
    assert int(jnp.sum(idx_r.free)) == dels.size
    assert int(jnp.sum(idx_r.graph.nbrs[dels] >= 0)) == 0


# --------------------------------------------------------- persistence
def _assert_same_search(a: UGIndex, b: UGIndex, nq=12):
    k1, k2 = jax.random.split(jax.random.key(21))
    qv = jax.random.normal(k1, (nq, a.x.shape[1]))
    c = jax.random.uniform(k2, (nq, 1))
    qi = jnp.concatenate(
        [jnp.maximum(c - 0.3, 0), jnp.minimum(c + 0.3, 1)], axis=1)
    for sem in (Semantics.IF, Semantics.IS):
        ra = a.search(qv, qi, sem=sem, ef=48, k=10)
        rb = b.search(qv, qi, sem=sem, ef=48, k=10)
        np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rb.ids))
        np.testing.assert_array_equal(np.asarray(ra.dist), np.asarray(rb.dist))


@pytest.fixture(scope="module")
def small_mutated(small_index):
    rng = np.random.default_rng(1)
    dels = jnp.asarray(rng.choice(small_index.n, size=25, replace=False)
                       .astype(np.int32))
    k = jax.random.key(2)
    new_x = jax.random.normal(k, (10, small_index.x.shape[1]))
    new_iv = iv.sample_uniform_intervals(jax.random.fold_in(k, 1), 10)
    return small_index.delete(dels).insert(new_x, new_iv)


def test_ckpt_roundtrip_mutated_bitwise(small_mutated, tmp_path):
    """ckpt-store save → restore of a mutated index: allocator state and
    search results are bitwise identical (ISSUE-4 satellite)."""
    from repro.ckpt import restore_index, save_index

    save_index(tmp_path / "ck", 3, small_mutated)
    back = restore_index(tmp_path / "ck")
    assert back.capacity == small_mutated.capacity
    np.testing.assert_array_equal(
        np.asarray(back.alive), np.asarray(small_mutated.alive))
    np.testing.assert_array_equal(
        np.asarray(back.free), np.asarray(small_mutated.free))
    _assert_same_search(small_mutated, back)


def test_npz_roundtrip_mutated_bitwise(small_mutated, tmp_path):
    small_mutated.save(tmp_path / "idx")
    back = UGIndex.load(tmp_path / "idx")
    assert back.n == small_mutated.n
    _assert_same_search(small_mutated, back)


def test_compact_repairs_deferred_tombstones(small_index):
    """compact() after delete(repair=False) must run the repair sweep first
    — dropping routable tombstones without bridging would sever paths."""
    rng = np.random.default_rng(8)
    dels = jnp.asarray(rng.choice(small_index.n, size=30, replace=False)
                       .astype(np.int32))
    a = small_index.delete(dels, repair=True).compact()
    b = small_index.delete(dels, repair=False).compact()
    np.testing.assert_array_equal(
        np.asarray(a.graph.nbrs), np.asarray(b.graph.nbrs))
    np.testing.assert_array_equal(
        np.asarray(a.graph.status), np.asarray(b.graph.status))


def test_compact_preserves_answers(small_mutated):
    """compact() drops dead slots and remaps ids: same answers, smaller
    arrays, static (mask-free) layout."""
    comp = small_mutated.compact()
    assert comp.alive is None and comp.capacity == small_mutated.n
    k1, k2 = jax.random.split(jax.random.key(33))
    qv = jax.random.normal(k1, (12, comp.x.shape[1]))
    c = jax.random.uniform(k2, (12, 1))
    qi = jnp.concatenate(
        [jnp.maximum(c - 0.3, 0), jnp.minimum(c + 0.3, 1)], axis=1)
    # remap old ids -> compacted ids to compare answer sets
    live = np.asarray(small_mutated.alive)
    remap = np.full((small_mutated.capacity,), -1, np.int64)
    remap[np.flatnonzero(live)] = np.arange(live.sum())
    for sem in (Semantics.IF, Semantics.IS):
        r_old = small_mutated.search(qv, qi, sem=sem, ef=48, k=10)
        r_new = comp.search(qv, qi, sem=sem, ef=48, k=10)
        ids_old = np.asarray(r_old.ids)
        mapped = np.where(ids_old >= 0, remap[np.clip(ids_old, 0, None)], -1)
        for row_m, row_n in zip(mapped, np.asarray(r_new.ids)):
            assert set(row_m[row_m >= 0]) == set(row_n[row_n >= 0]), sem


# ------------------------------------------------------------- serving
def test_engine_upsert_remove_bucketing(small_index):
    """ServeEngine streaming path: bucketed upsert/remove keep the index
    consistent; pad rows allocate nothing and are reclaimed next insert."""
    from repro.serve.engine import ServeEngine

    engine = ServeEngine.__new__(ServeEngine)   # no LM tower needed here
    engine.index = None
    engine.search_backend = "xla"
    engine.search_width = 4
    engine.attach_index(small_index)
    n0 = small_index.n

    k = jax.random.key(41)
    new_x = jax.random.normal(k, (5, small_index.x.shape[1]))
    new_iv = iv.sample_uniform_intervals(jax.random.fold_in(k, 1), 5)
    engine.upsert(None, new_iv, x=new_x)        # pads 5 -> bucket of 8
    assert engine.index.n == n0 + 5
    engine.remove(jnp.arange(3, dtype=jnp.int32))
    assert engine.index.n == n0 + 5 - 3
    res = engine.retrieve(None, jnp.asarray([[0.0, 1.0]] * 5),
                          sem=Semantics.IF, ef=48, k=5, q_v=new_x)
    ids = np.asarray(res.ids)
    assert not np.isin(ids[ids >= 0], [0, 1, 2]).any()
    # pad slots from the bucketed upsert are free for the next batch
    assert engine.index.capacity >= n0 + 8


def test_pipeline_forward_subprocess():
    """GPipe pipeline == sequential stack (8 fake devices, subprocess)."""
    from tests.test_distributed import run_sub

    run_sub(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_forward, bubble_fraction
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("stage",))
n_stages, per, d = 4, 2, 16
key = jax.random.key(0)
Ws = jax.random.normal(key, (n_stages, per, d, d)) * (1.0 / d ** 0.5)

def stage_fn(p, x):
    for i in range(per):
        x = jnp.tanh(x @ p[i])
    return x

x = jax.random.normal(jax.random.fold_in(key, 1), (8, 4, d))  # 8 microbatches
out = pipeline_forward(mesh, "stage", stage_fn, Ws, x)

ref = x
for s in range(n_stages):
    ref = jax.vmap(lambda mb: stage_fn(Ws[s], mb))(ref)
err = float(jnp.max(jnp.abs(out - ref)))
assert err < 1e-5, err
assert abs(bubble_fraction(8, 4) - 3/11) < 1e-9
print("pipeline OK", err)
"""
    )
