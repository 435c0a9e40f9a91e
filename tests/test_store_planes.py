"""IndexStore / vector-plane suite (DESIGN.md §12, ISSUE-5 acceptance).

Pins the unified-store contracts:

* **buffer identity** — an f32 index's ``x`` view IS the plane buffer, and
  a ServeEngine holds the attached store by reference (zero duplicate
  device copies across attach + retrieve);
* **cross-dtype parity** — ``bf16``/``int8`` scan planes on the *same
  graph* stay within tolerance of the f32 plane, and ``int8`` + the f32
  rerank plane matches the f32 top-k quality (≤ 0.02 recall loss);
* **quantized kernels** — the int8 expand-score Pallas kernel and its XLA
  twin are bit-identical and chunk-invariant, and the traced search step
  materializes no ``(B, C, d)`` gather on the quantized plane either;
* **persistence** — npz and ckpt-store round trips preserve quantization
  parameters and codes bitwise (codes are meaningless under any other
  scale/zero).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Semantics, UGConfig, UGIndex, recall
from repro.core import intervals as iv
from repro.core.store import (
    PQ_K, VectorPlane, default_pq_m, quantization_params,
    train_pq_codebooks,
)
from repro.kernels import ops

pytestmark = pytest.mark.hermetic  # parity suite for the no-hypothesis job

CFG = UGConfig(ef_spatial=16, ef_attribute=32, max_edges_if=12,
               max_edges_is=12, iterations=2, repair_width=8,
               exact_spatial=True, block=256)


@pytest.fixture(scope="module")
def plane_index():
    k1, k2 = jax.random.split(jax.random.key(3))
    n, d = 360, 12
    x = jax.random.normal(k1, (n, d))
    ints = iv.sample_uniform_intervals(k2, n)
    return UGIndex.build(x, ints, CFG)


@pytest.fixture(scope="module")
def plane_queries(plane_index):
    k1, k2 = jax.random.split(jax.random.key(13))
    nq = 24
    qv = jax.random.normal(k1, (nq, plane_index.store.dim))
    c = jax.random.uniform(k2, (nq, 1))
    qi = jnp.concatenate(
        [jnp.maximum(c - 0.3, 0), jnp.minimum(c + 0.3, 1)], axis=1)
    return qv, qi


# ------------------------------------------------------------ store basics
def test_f32_plane_is_identity_view(plane_index):
    """For an f32 plane, ``UGIndex.x`` and ``plane.decode()`` are the SAME
    buffer — no copy anywhere on the static path."""
    st = plane_index.store
    assert st.plane.tag == "f32"
    assert plane_index.x is st.plane.data
    assert st.plane.decode() is st.plane.data
    assert st.vectors_f32() is st.plane.data


def test_quantization_roundtrip_error_bound(plane_index):
    x = plane_index.x
    plane = VectorPlane.encode(x, "int8")
    err = jnp.abs(plane.decode() - x)
    # affine per-dim quantization: |err| <= scale/2 (+ float slop)
    assert bool(jnp.all(err <= plane.scale[None, :] * 0.5 + 1e-6))
    # frozen-parameter row encoding matches full-plane encoding bitwise
    rows = plane.encode_rows(x[:7])
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(plane.data[:7]))


def test_plane_bytes_per_vector(plane_index):
    d = plane_index.store.dim
    f32 = plane_index.store.plane.bytes_per_vector()
    bf16 = VectorPlane.encode(plane_index.x, "bf16").bytes_per_vector()
    q8 = VectorPlane.encode(plane_index.x, "int8").bytes_per_vector()
    assert f32 == 4 * d
    assert bf16 == 2 * d
    assert f32 / q8 >= 3.0  # the ISSUE-5 ≥3x scan-bytes reduction


# --------------------------------------------------------- cross-dtype parity
def test_cross_dtype_recall_parity(plane_index, plane_queries):
    """bf16 / int8 planes on the same graph stay near the f32 plane; int8 +
    f32 rerank stays within 0.02 of f32 (the ISSUE-5 acceptance bound)."""
    qv, qi = plane_queries
    for sem in (Semantics.IF, Semantics.RS):
        q = qi if sem is Semantics.IF else jnp.concatenate(
            [qi[:, :1], qi[:, :1]], axis=1)
        gt = plane_index.ground_truth(qv, q, sem=sem, k=10)
        r_f32 = recall(plane_index.search(qv, q, sem=sem, ef=64, k=10), gt)
        r_bf16 = recall(
            plane_index.with_dtype("bf16").search(qv, q, sem=sem, ef=64, k=10),
            gt)
        r_q8rr = recall(
            plane_index.with_dtype("int8", rerank=True)
            .search(qv, q, sem=sem, ef=64, k=10), gt)
        assert r_bf16 >= r_f32 - 0.05, (sem, r_bf16, r_f32)
        assert r_q8rr >= r_f32 - 0.02, (sem, r_q8rr, r_f32)


def test_int8_without_rerank_still_searches(plane_index, plane_queries):
    qv, qi = plane_queries
    idx8 = plane_index.with_dtype("int8", rerank=False)
    assert idx8.store.rerank is None
    gt = plane_index.ground_truth(qv, qi, sem=Semantics.IF, k=10)
    r = recall(idx8.search(qv, qi, sem=Semantics.IF, ef=64, k=10), gt)
    r_f32 = recall(plane_index.search(qv, qi, sem=Semantics.IF, ef=64, k=10), gt)
    assert r >= r_f32 - 0.1, (r, r_f32)


# ------------------------------------------------------------ int8 kernels
def test_expand_score_q_backends_bitwise():
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    n, d, B, C = 257, 19, 6, 23
    x = jax.random.normal(k1, (n, d))
    plane = VectorPlane.encode(x, "int8")
    q = jax.random.normal(k2, (B, d))
    idx = jax.random.randint(k3, (B, C), -2, n)
    outs = {
        b: np.asarray(ops.expand_score_plane(plane, idx, q, backend=b))
        for b in ("pallas", "xla")
    }
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    assert np.isinf(outs["xla"][np.asarray(idx) < 0]).all()
    # chunk invariance of the xla twin (elementwise reduction contract)
    from repro.kernels.expand_score import expand_score_q_xla

    for chunk in (1, 5, 11):
        np.testing.assert_array_equal(
            np.asarray(expand_score_q_xla(
                plane.data, plane.scale, plane.zero, idx, q, chunk=chunk)),
            outs["xla"])
    # legacy agrees numerically (matmul identity: allclose only)
    legacy = np.asarray(ops.expand_score_plane(plane, idx, q, backend="legacy"))
    fin = np.isfinite(outs["xla"])
    np.testing.assert_allclose(legacy[fin], outs["xla"][fin], atol=1e-3)


def test_search_step_profile_int8():
    """The quantized plane carries the same traced-memory guarantee: no
    (B, C, d) gather, no (·, C, C) dedup tensor (DESIGN.md §12)."""
    from repro.core.search import search_step_memory_profile

    for backend in ("xla", "pallas"):
        prof = search_step_memory_profile(backend, dtype="int8")
        assert not prof["gather_bcd"], backend
        assert not prof["quadratic_cc"], backend
    legacy = search_step_memory_profile("legacy", dtype="int8")
    assert legacy["gather_bcd"] and legacy["quadratic_cc"]


def test_mixed_search_on_quantized_plane(plane_index, plane_queries):
    """Runtime-semantics batches work unchanged on a quantized store."""
    qv, qi = plane_queries
    idx8 = plane_index.with_dtype("int8", rerank=True)
    sems = [Semantics.IF, Semantics.IS] * (qv.shape[0] // 2)
    res = idx8.search_mixed(qv, qi, sems, ef=48, k=10)
    for s in (Semantics.IF, Semantics.IS):
        sel = np.asarray([i for i, ss in enumerate(sems) if ss is s])
        ref = idx8.search(qv[sel], qi[sel], sem=s, ef=48, k=10)
        np.testing.assert_array_equal(
            np.asarray(res.ids)[sel], np.asarray(ref.ids))


# ------------------------------------------------------------- persistence
def _assert_store_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a.plane.data),
                                  np.asarray(b.plane.data))
    assert a.plane.tag == b.plane.tag
    for f in ("scale", "zero", "codebooks"):
        av, bv = getattr(a.plane, f), getattr(b.plane, f)
        assert (av is None) == (bv is None)
        if av is not None:
            np.testing.assert_array_equal(np.asarray(av), np.asarray(bv))
    assert (a.rerank is None) == (b.rerank is None)
    if a.rerank is not None:
        np.testing.assert_array_equal(np.asarray(a.rerank.data),
                                      np.asarray(b.rerank.data))


def test_npz_roundtrip_preserves_quantization_bitwise(plane_index, plane_queries, tmp_path):
    idx8 = plane_index.with_dtype("int8", rerank=True)
    idx8.save(tmp_path / "q")
    back = UGIndex.load(tmp_path / "q")
    _assert_store_bitwise(idx8.store, back.store)
    qv, qi = plane_queries
    ra = idx8.search(qv, qi, sem=Semantics.IS, ef=48, k=10)
    rb = back.search(qv, qi, sem=Semantics.IS, ef=48, k=10)
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rb.ids))
    np.testing.assert_array_equal(np.asarray(ra.dist), np.asarray(rb.dist))


def test_ckpt_roundtrip_preserves_quantization_bitwise(plane_index, tmp_path):
    from repro.ckpt import restore_index, save_index

    idx8 = plane_index.with_dtype("int8", rerank=True)
    save_index(tmp_path / "ck", 1, idx8)
    back = restore_index(tmp_path / "ck")
    _assert_store_bitwise(idx8.store, back.store)
    assert back.dtype == "int8"


def test_bf16_roundtrips_npz_and_ckpt(plane_index, tmp_path):
    """bf16 codes survive both persistence paths bitwise (numpy cannot
    serialize ml_dtypes bfloat16 natively — stored as a uint16 bit view)."""
    from repro.ckpt import restore_index, save_index

    idxb = plane_index.with_dtype("bf16")
    idxb.save(tmp_path / "npz")
    back = UGIndex.load(tmp_path / "npz")
    assert back.dtype == "bf16"
    assert back.store.plane.data.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(idxb.store.plane.data).view(np.uint16),
        np.asarray(back.store.plane.data).view(np.uint16))
    save_index(tmp_path / "ck", 2, idxb)
    back2 = restore_index(tmp_path / "ck")
    assert back2.store.plane.data.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(idxb.store.plane.data).view(np.uint16),
        np.asarray(back2.store.plane.data).view(np.uint16))


def test_shard_index_qparams_ignore_pad_rows(plane_index):
    """Host-assembled sharded stores derive int8 params from real rows only
    — the builder's zero pad rows must not widen the per-dim ranges."""
    from jax.sharding import Mesh
    from repro.core.sharded import shard_index

    x = np.asarray(plane_index.x) + 5.0          # offset: 0-pads are outliers
    n, d = x.shape
    ints = np.asarray(plane_index.intervals)
    nbrs = np.asarray(plane_index.store.nbrs)
    stat = np.asarray(plane_index.store.status)
    # append one zero pad row (gid = -1), as build_sharded_index_host does
    xp = np.concatenate([x, np.zeros((1, d), x.dtype)])
    ip = np.concatenate([ints, np.asarray([[2.0, -2.0]], ints.dtype)])
    nbp = np.concatenate([nbrs, np.full((1, nbrs.shape[1]), -1, nbrs.dtype)])
    stp = np.concatenate([stat, np.zeros((1, stat.shape[1]), stat.dtype)])
    gid = np.concatenate([np.arange(n, dtype=np.int32), [-1]])
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    sidx = shard_index(mesh, ("data",), xp, ip, nbp, stp, gid, dtype="int8")
    want_scale, want_zero = quantization_params(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(sidx.store.plane.scale),
                                  np.asarray(want_scale))
    np.testing.assert_array_equal(np.asarray(sidx.store.plane.zero),
                                  np.asarray(want_zero))
    # pq codebooks follow the same rule: trained over real rows only,
    # replicated across shards like the int8 qparams
    sidx_pq = shard_index(mesh, ("data",), xp, ip, nbp, stp, gid, dtype="pq")
    assert sidx_pq.store.plane.tag == "pq"
    want_cb = train_pq_codebooks(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(sidx_pq.store.plane.codebooks),
                                  np.asarray(want_cb))


# ----------------------------------------------------------------- serving
def test_engine_holds_store_by_reference(plane_index, plane_queries):
    """attach_index + retrieve share the attached store's device buffers —
    one store, zero duplicate device copies (ISSUE-5 satellite)."""
    from repro.serve.engine import ServeEngine

    engine = ServeEngine.__new__(ServeEngine)  # no LM tower needed here
    engine.index = None
    engine.search_backend = "xla"
    engine.search_width = 4
    engine.attach_index(plane_index)
    assert engine.index is plane_index
    assert engine.index.store is plane_index.store
    qv, qi = plane_queries
    res = engine.retrieve(None, qi, sem=Semantics.IF, ef=48, k=10, q_v=qv)
    assert res.ids.shape == (qv.shape[0], 10)
    # retrieve did not re-materialize or swap any store buffer
    assert engine.index.store is plane_index.store
    assert engine.index.store.plane.data is plane_index.store.plane.data
    ptr = lambda a: a.unsafe_buffer_pointer()
    assert ptr(engine.index.store.plane.data) == ptr(plane_index.store.plane.data)
    assert ptr(engine.index.store.nbrs) == ptr(plane_index.store.nbrs)


# ---------------------------------------------------------------- updates
def test_insert_into_quantized_store(plane_index):
    """Streaming inserts encode rows under the frozen quantization params;
    the allocator lives on the store (grow keeps scale/zero buffers)."""
    idx8 = plane_index.with_dtype("int8", rerank=True)
    scale0, zero0 = idx8.store.plane.scale, idx8.store.plane.zero
    new_x = jnp.full((3, idx8.store.dim), 0.33, jnp.float32)
    new_iv = jnp.asarray([[0.2, 0.8]] * 3)
    idx2 = idx8.insert(new_x, new_iv)
    assert idx2.n == idx8.n + 3
    assert idx2.store.plane.tag == "int8"
    np.testing.assert_array_equal(np.asarray(idx2.store.plane.scale),
                                  np.asarray(scale0))
    np.testing.assert_array_equal(np.asarray(idx2.store.plane.zero),
                                  np.asarray(zero0))
    # inserted rows are findable, and the rerank plane keeps them exact
    hit = idx2.search(new_x[:1], jnp.asarray([[0.0, 1.0]]),
                      sem=Semantics.IF, ef=48, k=1)
    slot = int(hit.ids[0, 0])
    assert slot >= 0
    np.testing.assert_allclose(
        np.asarray(idx2.store.rerank.data[slot]), 0.33, atol=1e-6)
    # delete + compact keep the plane consistent
    idx3 = idx2.delete(jnp.asarray([slot])).compact()
    assert idx3.store.plane.data.shape[0] == idx3.n
    assert idx3.store.rerank.data.shape[0] == idx3.n


def test_quantization_params_shapes(plane_index):
    scale, zero = quantization_params(plane_index.x)
    assert scale.shape == (plane_index.store.dim,)
    assert zero.shape == (plane_index.store.dim,)
    assert bool(jnp.all(scale > 0))


# ----------------------------------------------------------------- pq plane
def test_default_pq_m_divides_dim():
    for d in (8, 12, 16, 24, 32, 48, 7, 11):
        m = default_pq_m(d)
        assert m >= 1 and d % m == 0, (d, m)
    assert default_pq_m(24) == 3
    assert default_pq_m(16) == 2


def test_pq_codebook_training_deterministic():
    """Codebook training is a pure function of (data, m, seed): two encodes
    of the same corpus agree bitwise, and frozen-codebook row encoding
    matches full-plane encoding bitwise (the streaming-insert contract)."""
    x = jax.random.normal(jax.random.key(21), (300, 24))
    a = VectorPlane.encode(x, "pq")
    b = VectorPlane.encode(x, "pq")
    m = default_pq_m(24)
    assert a.codebooks.shape == (m, PQ_K, 24 // m)
    assert a.data.shape == (300, m) and a.data.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(a.codebooks),
                                  np.asarray(b.codebooks))
    np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
    rows = a.encode_rows(x[:9])
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(a.data[:9]))
    # encoding under pre-trained codebooks (the sharded path) is the same
    cb = train_pq_codebooks(x)
    c = VectorPlane.encode(x, "pq", qparams=cb)
    np.testing.assert_array_equal(np.asarray(c.data), np.asarray(a.data))


def test_pq_nearest_chunks_match_one_slab(monkeypatch):
    """The nearest-centroid search in row chunks (ragged last chunk
    padded) returns the ids one whole-sample argmin returns.  Integer
    coordinates keep every distance exact, so both agree bit for bit."""
    import repro.core.store as store

    rng = np.random.default_rng(23)
    xs = jnp.asarray(rng.integers(-8, 9, (3, 1000, 2)), jnp.float32)
    cb = jnp.asarray(rng.integers(-8, 9, (3, PQ_K, 2)), jnp.float32)
    whole = np.asarray(jnp.argmin(store._pq_sq_dists(xs, cb), axis=-1))
    monkeypatch.setattr(store, "_PQ_SLAB_BYTES", 3 * PQ_K * 4 * 96)  # 64 rows
    np.testing.assert_array_equal(np.asarray(store._pq_nearest(xs, cb)), whole)


def test_pq_decode_roundtrip_reasonable():
    x = jax.random.normal(jax.random.key(22), (400, 24))
    plane = VectorPlane.encode(x, "pq")
    assert plane.dim == 24
    dec = plane.decode()
    assert dec.shape == x.shape and dec.dtype == jnp.float32
    rel = float(jnp.linalg.norm(dec - x) / jnp.linalg.norm(x))
    assert rel < 0.5, rel    # coarse codes, but far from garbage
    np.testing.assert_array_equal(np.asarray(plane.decode_rows(jnp.arange(5))),
                                  np.asarray(dec[:5]))


def test_expand_score_pq_backends_bitwise():
    """The Pallas LUT kernel and its chunked XLA twin agree bitwise, across
    chunk widths and batch composition, and honor the shared LUT path."""
    k1, k2, k3 = jax.random.split(jax.random.key(9), 3)
    n, d, B, C = 257, 24, 6, 23
    x = jax.random.normal(k1, (n, d))
    plane = VectorPlane.encode(x, "pq")
    q = jax.random.normal(k2, (B, d))
    idx = jax.random.randint(k3, (B, C), -2, n)
    outs = {
        b: np.asarray(ops.expand_score_plane(plane, idx, q, backend=b))
        for b in ("pallas", "xla")
    }
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    assert np.isinf(outs["xla"][np.asarray(idx) < 0]).all()
    from repro.kernels.expand_score import expand_score_pq_xla

    # chunk invariance of the xla twin (elementwise LUT-gather contract)
    for chunk in (1, 3, 7, 19, 32):
        np.testing.assert_array_equal(
            np.asarray(expand_score_pq_xla(
                plane.data, plane.codebooks, idx, q, chunk=chunk)),
            outs["xla"])
    # batch composition: each row scored alone matches its slice of the batch
    for b in ("pallas", "xla"):
        for i in range(B):
            np.testing.assert_array_equal(
                np.asarray(ops.expand_score_plane(
                    plane, idx[i:i + 1], q[i:i + 1], backend=b))[0],
                outs[b][i])
    # precomputed-LUT path (what the fused step uses) is the same program
    lut = ops.pq_lut(plane, q)
    assert lut.shape == (B, plane.codebooks.shape[0], PQ_K)
    for b in ("pallas", "xla"):
        np.testing.assert_array_equal(
            np.asarray(ops.expand_score_plane(plane, idx, q, backend=b,
                                              lut=lut)),
            outs[b])
    # legacy decode-then-score agrees numerically (different f32 association
    # order between the m-fold ADC sum and the d-fold decoded sum: allclose)
    legacy = np.asarray(ops.expand_score_plane(plane, idx, q, backend="legacy"))
    fin = np.isfinite(outs["xla"])
    np.testing.assert_allclose(legacy[fin], outs["xla"][fin], rtol=1e-4,
                               atol=1e-3)


def test_search_step_profile_pq():
    """The pq step keeps the traced-memory contract: no (B, C, d) gather,
    no (·, C, C) dedup tensor, and — the ADC guarantee — no decoded f32
    (n, d) corpus anywhere in the jaxpr."""
    from repro.core.search import search_step_memory_profile

    for backend in ("xla", "pallas"):
        prof = search_step_memory_profile(backend, dtype="pq")
        assert not prof["gather_bcd"], backend
        assert not prof["quadratic_cc"], backend
        assert not prof["decoded_nd"], backend
    legacy = search_step_memory_profile("legacy", dtype="pq")
    assert legacy["gather_bcd"] and legacy["quadratic_cc"]
    assert legacy["decoded_nd"]


def test_pq_rerank_recall_parity(plane_index, plane_queries):
    """pq + f32 rerank stays within 0.05 of the f32 plane on the same graph
    (the ISSUE-7 acceptance bound)."""
    qv, qi = plane_queries
    idxpq = plane_index.with_dtype("pq")
    assert idxpq.dtype == "pq" and idxpq.store.rerank is not None
    for sem in (Semantics.IF, Semantics.IS):
        gt = plane_index.ground_truth(qv, qi, sem=sem, k=10)
        r_f32 = recall(plane_index.search(qv, qi, sem=sem, ef=64, k=10), gt)
        r_pq = recall(idxpq.search(qv, qi, sem=sem, ef=64, k=10), gt)
        assert r_pq >= r_f32 - 0.05, (sem, r_pq, r_f32)


def test_insert_into_pq_store(plane_index):
    """Streaming inserts encode rows under the *frozen* codebooks — the
    same contract as int8 scale/zero — and compact keeps them attached."""
    idxpq = plane_index.with_dtype("pq")
    cb0 = np.asarray(idxpq.store.plane.codebooks)
    new_x = jnp.full((3, idxpq.store.dim), 0.33, jnp.float32)
    new_iv = jnp.asarray([[0.2, 0.8]] * 3)
    idx2 = idxpq.insert(new_x, new_iv)
    assert idx2.n == idxpq.n + 3
    assert idx2.store.plane.tag == "pq"
    np.testing.assert_array_equal(np.asarray(idx2.store.plane.codebooks), cb0)
    # inserted codes match a frozen-codebook re-encode of the same rows
    slot_codes = idx2.store.plane.encode_rows(new_x)
    hit = idx2.search(new_x[:1], jnp.asarray([[0.0, 1.0]]),
                      sem=Semantics.IF, ef=48, k=1)
    slot = int(hit.ids[0, 0])
    assert slot >= 0
    np.testing.assert_array_equal(np.asarray(idx2.store.plane.data[slot]),
                                  np.asarray(slot_codes[0]))
    idx3 = idx2.delete(jnp.asarray([slot])).compact()
    assert idx3.store.plane.data.shape[0] == idx3.n
    np.testing.assert_array_equal(np.asarray(idx3.store.plane.codebooks), cb0)


def test_pq_roundtrips_npz_and_ckpt(plane_index, plane_queries, tmp_path):
    from repro.ckpt import restore_index, save_index

    idxpq = plane_index.with_dtype("pq")
    idxpq.save(tmp_path / "npz")
    back = UGIndex.load(tmp_path / "npz")
    assert back.dtype == "pq"
    _assert_store_bitwise(idxpq.store, back.store)
    qv, qi = plane_queries
    ra = idxpq.search(qv, qi, sem=Semantics.IF, ef=48, k=10)
    rb = back.search(qv, qi, sem=Semantics.IF, ef=48, k=10)
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rb.ids))
    save_index(tmp_path / "ck", 3, idxpq)
    back2 = restore_index(tmp_path / "ck")
    assert back2.dtype == "pq"
    _assert_store_bitwise(idxpq.store, back2.store)


def test_pq_bytes_per_vector_reduction(plane_index):
    """Codes shrink scan bytes by 4d/m (>= 8x for the default m); the
    amortized figure includes the fixed codebook overhead."""
    x = jax.random.normal(jax.random.key(30), (512, 24))
    plane = VectorPlane.encode(x, "pq")
    m = plane.codebooks.shape[0]
    code_bytes = plane.data.shape[0] * m
    assert (4 * 24 * 512) / code_bytes >= 8.0
    bpv = plane.bytes_per_vector()
    assert bpv == (code_bytes + plane.codebooks.size * 4) / 512


# ------------------------------------------------- accounting regressions
def test_bytes_per_vector_across_grow(plane_index):
    """ISSUE-7 satellite: bytes/vec must amortize over *live* rows, not
    capacity — after grow() doubles the buffers the reported figure rises
    (fixed overhead over the same live set), it must never halve."""
    d = plane_index.store.dim
    before = plane_index.vector_memory_bytes()["plane_bytes_per_vector"]
    assert before == 4 * d
    new_x = jnp.full((3, d), 0.25, jnp.float32)
    new_iv = jnp.asarray([[0.1, 0.9]] * 3)
    idx2 = plane_index.insert(new_x, new_iv)     # static index: forces grow
    assert idx2.capacity > plane_index.capacity
    after = idx2.vector_memory_bytes()["plane_bytes_per_vector"]
    assert after >= 4 * d                        # never below the row cost
    want = 4 * d * idx2.capacity / idx2.n
    assert abs(after - want) < 1e-6, (after, want)
    # capacity-denominated (the old bug) would report exactly 4*d here
    assert after > 4 * d * 1.5


def test_masks_memory_bytes_accounting(plane_index):
    """ISSUE-7 satellite: masks bytes charge 1 byte/slot per *present*
    mask — alive-only stores must not be billed for a free mask."""
    st = plane_index.store
    cap = st.capacity
    assert st.memory_bytes()["masks"] == 0            # static: no masks
    alive = jnp.ones((cap,), bool)
    assert st.replace(alive=alive).memory_bytes()["masks"] == cap
    both = st.replace(alive=alive, free=jnp.zeros((cap,), bool))
    assert both.memory_bytes()["masks"] == 2 * cap
    assert st.live_count() == cap
    assert both.replace(alive=alive.at[0].set(False)).live_count() == cap - 1
