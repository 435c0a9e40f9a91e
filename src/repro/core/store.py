"""IndexStore — the one typed pytree every layer of the index shares.

Before this module the repo carried three divergent index representations
(the dense ``UGIndex`` field bundle, ``ShardedIndexArrays``, and the
``ServeEngine``'s attached copies) and every layer hand-carried
``(x, intervals, nbrs, status, alive, …)`` tuples.  ``IndexStore`` unifies
them (DESIGN.md §12): one registered pytree holding

* a **vector plane** — the scoring representation of the corpus vectors.
  Four plane tags: ``f32`` (paper-faithful), ``bf16`` (2 bytes/dim, cast
  in-register by the existing expand-score kernels), ``int8``
  (scalar-quantized, per-dimension affine ``x ≈ q·scale + zero``,
  dequantized in-register by the quantized kernel twins), and ``pq``
  (product-quantized: ``m`` subspaces of ``d/m`` dims, 256 k-means
  centroids each, one uint8 code per subspace, scored through per-query
  lookup tables — DESIGN.md §14);
* an optional **fp32 rerank plane** — exact vectors used only to re-score
  the final beam, so a quantized scan plane keeps f32-grade top-k;
* the graph (``nbrs``/``status``), the interval column, the entry
  structure (Alg. 5), and the streaming allocator state (``alive``/``free``
  masks, DESIGN.md §11).

Being a pytree, the store traces through ``jax.jit`` and ``shard_map``
unchanged — the sharded serving path holds the *same* structure with
row-sharded leaves (core/sharded.py), and the serve engine holds it by
reference (zero duplicate device copies; tests/test_store_planes.py pins
buffer identity).

Quantization scheme (``int8``): per-dimension affine with
``zero = (min + max) / 2`` and ``scale = (max - min) / 254`` (floored at
1e-8), so codes span ``[-127, 127]`` symmetrically around the per-dim
center.  Parameters are frozen at encode time; streaming inserts encode
new rows under the frozen parameters (re-centering would invalidate every
stored code).  Decode error is ≤ ``scale/2`` per dimension.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.entry import EntryIndex, build_entry_index
from repro.core.exact import DenseGraph

PLANE_TAGS = ("f32", "bf16", "int8", "pq")
_PLANE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
_QMAX = 127.0  # int8 code range is [-127, 127]; -128 stays unused (symmetric)
PQ_K = 256     # centroids per subspace — one uint8 code each
# 128 training rows per centroid: with 16 (a 4,096-row sample) the k-means
# overfits its sample and pq recall falls with corpus size.
_PQ_TRAIN_SAMPLE = 32768
_PQ_TRAIN_ITERS = 10
# Nearest-centroid searches run over row chunks whose (m, rows, 256) f32
# distance slab stays within this size.  Whole-sample slabs of 1.6 GB and
# more came back wrong from a TPU v5e: codebooks trained that way had 46%
# more reconstruction error than the same training on the CPU.
_PQ_SLAB_BYTES = 64 << 20
# pq training and encoding matmuls run in full f32.  A TPU's default f32
# matmul rounds its operands to bf16, which assigns about 8% of the 2-d
# subvectors of a 96-d row to the wrong centroid; on the CPU this changes
# nothing.
_EXACT = jax.lax.Precision.HIGHEST


def quantization_params(
    x: jnp.ndarray, mask: jnp.ndarray | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-dimension affine (scale, zero) from the corpus column ranges
    (over the rows where ``mask`` is set, when given)."""
    x32 = x.astype(jnp.float32)
    if mask is None:
        lo = jnp.min(x32, axis=0)
        hi = jnp.max(x32, axis=0)
    else:
        lo = jnp.min(jnp.where(mask[:, None], x32, jnp.inf), axis=0)
        hi = jnp.max(jnp.where(mask[:, None], x32, -jnp.inf), axis=0)
    zero = (lo + hi) * 0.5
    scale = jnp.maximum((hi - lo) / (2.0 * _QMAX), 1e-8)
    return scale, zero


def default_pq_m(d: int) -> int:
    """Default subspace count: ~8 dims per subspace, reduced until it
    divides ``d`` evenly (d=24 → m=3, d=16 → m=2, d=12 → m=1)."""
    m = max(d // 8, 1)
    while d % m:
        m -= 1
    return m


def _pq_sq_dists(xs: jnp.ndarray, cb: jnp.ndarray) -> jnp.ndarray:
    """(m, s, K) squared distances from subvectors to centroids."""
    return (
        jnp.sum(xs * xs, axis=-1)[:, :, None]
        - 2.0 * jnp.einsum("msd,mkd->msk", xs, cb, precision=_EXACT)
        + jnp.sum(cb * cb, axis=-1)[:, None, :]
    )


def _pq_nearest(xs: jnp.ndarray, cb: jnp.ndarray) -> jnp.ndarray:
    """``(m, s, dsub)`` subvectors → ``(m, s)`` int32 nearest-centroid ids,
    in ``lax.map`` steps of at most ``_PQ_SLAB_BYTES`` of distances."""
    m, s, dsub = xs.shape
    rows = 1 << max((_PQ_SLAB_BYTES // (m * cb.shape[1] * 4)).bit_length() - 1, 0)
    if s <= rows:
        return jnp.argmin(_pq_sq_dists(xs, cb), axis=-1)
    xp = jnp.pad(xs, ((0, 0), (0, -s % rows), (0, 0)))
    chunks = xp.reshape(m, -1, rows, dsub).transpose(1, 0, 2, 3)
    near = jax.lax.map(lambda c: jnp.argmin(_pq_sq_dists(c, cb), axis=-1), chunks)
    return near.transpose(1, 0, 2).reshape(m, -1)[:, :s]


@jax.jit
def _pq_encode(rows: jnp.ndarray, codebooks: jnp.ndarray) -> jnp.ndarray:
    """``(b, d)`` rows → ``(b, m)`` uint8 codes (nearest centroid per
    subspace)."""
    m, _, dsub = codebooks.shape
    r = rows.astype(jnp.float32).reshape(rows.shape[0], m, dsub)
    return _pq_nearest(r.transpose(1, 0, 2), codebooks).T.astype(jnp.uint8)


@jax.jit
def _pq_lloyd(xs: jnp.ndarray, cb: jnp.ndarray) -> jnp.ndarray:
    """``_PQ_TRAIN_ITERS`` Lloyd iterations over every subspace at once.
    Empty clusters keep their previous centroid (no reseeding — keeps the
    training deterministic and jit-friendly)."""

    def step(cb, _):
        assign = _pq_nearest(xs, cb)                                # (m, s)
        onehot = jax.nn.one_hot(assign, cb.shape[1], dtype=jnp.float32)
        counts = jnp.sum(onehot, axis=1)                            # (m, K)
        sums = jnp.einsum("msk,msd->mkd", onehot, xs,
                          precision=_EXACT)                         # (m, K, dsub)
        new = sums / jnp.maximum(counts[..., None], 1.0)
        return jnp.where((counts > 0)[..., None], new, cb), None

    cb, _ = jax.lax.scan(step, cb, None, length=_PQ_TRAIN_ITERS)
    return cb


def train_pq_codebooks(
    x: jnp.ndarray, m: int | None = None, *, seed: int = 0
) -> jnp.ndarray:
    """On-device k-means codebook training: ``(m, 256, d/m)`` f32.

    Trains on a deterministic (seeded) sample of ≤ ``_PQ_TRAIN_SAMPLE`` rows,
    initialized from distinct permuted sample rows per subspace.  The
    result is **frozen** at encode time exactly like the int8 qparams —
    streaming inserts encode new rows under the frozen codebooks
    (retraining would invalidate every stored code)."""
    n, d = x.shape
    if m is None:
        m = default_pq_m(d)
    if m < 1 or d % m:
        raise ValueError(f"pq subspace count m={m} must divide d={d}")
    s = max(min(n, _PQ_TRAIN_SAMPLE), 1)
    # The sample ids are drawn on the host (a device permutation of a
    # million ids is a sort that compiles for about a minute on a TPU), and
    # only the sampled rows move.
    perm = np.random.default_rng(seed).permutation(max(n, 1))[:s]
    xs = jnp.asarray(x[perm]).astype(jnp.float32)
    xs = xs.reshape(s, m, d // m).transpose(1, 0, 2)          # (m, s, dsub)
    init = xs[:, jnp.arange(PQ_K) % s, :]                    # (m, K, dsub)
    return _pq_lloyd(xs, init)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class VectorPlane:
    """One storage representation of the corpus vectors.

    ``tag`` is pytree aux data (a compile-time constant), so kernel
    dispatch on the plane dtype never retraces on array contents — only a
    different tag compiles a different program.
    """

    tag: str                        # "f32" | "bf16" | "int8" | "pq"
    data: jnp.ndarray               # (cap, d) in the plane dtype; pq: (cap, m) u8
    scale: jnp.ndarray | None = None  # (d,) f32 — int8 only
    zero: jnp.ndarray | None = None   # (d,) f32 — int8 only
    codebooks: jnp.ndarray | None = None  # (m, 256, d/m) f32 — pq only

    def tree_flatten(self):
        return (self.data, self.scale, self.zero, self.codebooks), self.tag

    @classmethod
    def tree_unflatten(cls, tag, children):
        data, scale, zero, codebooks = children
        return cls(tag, data, scale, zero, codebooks)

    # ------------------------------------------------------------- encode
    @classmethod
    def encode(
        cls, x: jnp.ndarray, tag: str, qparams=None, *, pq_m: int | None = None
    ) -> "VectorPlane":
        """Encode f32 vectors into a plane; ``qparams`` overrides the
        derived int8 (scale, zero) — or, for ``pq``, the trained
        ``(m, 256, d/m)`` codebooks — used to re-encode rows of a grown
        capacity under frozen parameters."""
        if tag not in PLANE_TAGS:
            raise ValueError(f"unknown plane tag {tag!r} (choices {PLANE_TAGS})")
        x = jnp.asarray(x)
        if tag == "f32":
            data = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
            return cls(tag, data)
        if tag == "bf16":
            return cls(tag, x.astype(jnp.bfloat16))
        if tag == "pq":
            cb = train_pq_codebooks(x, pq_m) if qparams is None else jnp.asarray(qparams)
            plane = cls(tag, jnp.zeros((0, cb.shape[0]), jnp.uint8), codebooks=cb)
            return dataclasses.replace(plane, data=plane.encode_rows(x))
        scale, zero = quantization_params(x) if qparams is None else qparams
        plane = cls(tag, jnp.zeros((0,), jnp.int8), scale, zero)
        return dataclasses.replace(plane, data=plane.encode_rows(x))

    def encode_rows(self, rows: jnp.ndarray) -> jnp.ndarray:
        """Encode f32 rows into this plane's dtype under its frozen params
        (streaming inserts; capacity growth)."""
        rows = jnp.asarray(rows)
        if self.tag == "f32":
            return rows if rows.dtype == jnp.float32 else rows.astype(jnp.float32)
        if self.tag == "bf16":
            return rows.astype(jnp.bfloat16)
        if self.tag == "pq":
            return _pq_encode(rows, self.codebooks)
        q = jnp.round((rows.astype(jnp.float32) - self.zero) / self.scale)
        return jnp.clip(q, -_QMAX, _QMAX).astype(jnp.int8)

    # ------------------------------------------------------------- decode
    def _pq_decode_codes(self, codes: jnp.ndarray) -> jnp.ndarray:
        """(b, m) uint8 codes → (b, d) f32 centroid reconstructions."""
        m, k, dsub = self.codebooks.shape
        flat = self.codebooks.reshape(m * k, dsub)
        idx = codes.astype(jnp.int32) + (jnp.arange(m, dtype=jnp.int32) * k)[None, :]
        return flat[idx].reshape(codes.shape[0], m * dsub)

    def decode(self) -> jnp.ndarray:
        """The (cap, d) f32 view.  Identity (same buffer) for ``f32``."""
        if self.tag == "f32":
            return self.data
        if self.tag == "bf16":
            return self.data.astype(jnp.float32)
        if self.tag == "pq":
            return self._pq_decode_codes(self.data)
        return self.data.astype(jnp.float32) * self.scale + self.zero

    def decode_rows(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Gather rows then dequantize — the (|ids|, d) f32 view of a row
        subset without materializing the full decoded plane."""
        rows = self.data[ids]
        if self.tag == "f32":
            return rows
        if self.tag == "bf16":
            return rows.astype(jnp.float32)
        if self.tag == "pq":
            return self._pq_decode_codes(rows)
        return rows.astype(jnp.float32) * self.scale + self.zero

    # -------------------------------------------------------------- stats
    @property
    def dim(self) -> int:
        if self.tag == "pq":
            m, _, dsub = self.codebooks.shape
            return m * dsub
        return self.data.shape[-1]

    def memory_bytes(self) -> int:
        b = self.data.size * self.data.dtype.itemsize
        for a in (self.scale, self.zero, self.codebooks):
            if a is not None:
                b += a.size * a.dtype.itemsize
        return int(b)

    def bytes_per_vector(self, n_live: int | None = None) -> float:
        """Amortized plane bytes per stored vector (qparams/codebooks
        included).  ``n_live`` is the live-row count; it defaults to the
        row capacity, but callers that grew the store must pass the live
        count — capacity doubling would otherwise silently halve the
        reported bytes/vec (the store itself owns the alive mask, so the
        plane cannot derive liveness here)."""
        n = self.data.shape[0] if n_live is None else n_live
        return self.memory_bytes() / max(n, 1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IndexStore:
    """The unified index pytree: planes + intervals + graph + entry +
    allocator.  Frozen — every mutation is a functional ``replace``."""

    plane: VectorPlane              # scoring plane (hot path)
    rerank: VectorPlane | None      # optional exact f32 plane (final top-k)
    intervals: jnp.ndarray          # (cap, 2)
    nbrs: jnp.ndarray               # (cap, M) int32, -1 padded
    status: jnp.ndarray             # (cap, M) uint8 semantic bitmask
    entry: EntryIndex | None        # Alg. 5 structure (None: built on use,
    #                                 e.g. per shard inside shard_map)
    alive: jnp.ndarray | None = None  # (cap,) bool; None = all live
    free: jnp.ndarray | None = None   # (cap,) bool; None = none free

    def tree_flatten(self):
        return (
            self.plane, self.rerank, self.intervals, self.nbrs, self.status,
            self.entry, self.alive, self.free,
        ), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    # -------------------------------------------------------------- views
    @property
    def capacity(self) -> int:
        return self.nbrs.shape[0]

    @property
    def dim(self) -> int:
        return self.plane.dim

    @property
    def graph(self) -> DenseGraph:
        """DenseGraph view over the same buffers (no copy)."""
        return DenseGraph(self.nbrs, self.status)

    def live_count(self) -> int:
        """Number of live rows (capacity when no alive mask is set)."""
        if self.alive is None:
            return self.capacity
        return int(jnp.sum(self.alive))

    def vectors_f32(self) -> jnp.ndarray:
        """Best-precision f32 vectors: the rerank plane when present, else
        the decoded scan plane.  Identity (same buffer) for an f32 plane."""
        if self.rerank is not None:
            return self.rerank.data
        return self.plane.decode()

    def replace(self, **kw) -> "IndexStore":
        return dataclasses.replace(self, **kw)

    # ---------------------------------------------------- slot allocator
    def masks(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Materialize the lazy all-live / none-free allocator masks."""
        cap = self.capacity
        alive = self.alive if self.alive is not None else jnp.ones((cap,), bool)
        free = self.free if self.free is not None else jnp.zeros((cap,), bool)
        return alive, free

    def widen_rows(self, m_full: int) -> "IndexStore":
        """Re-widen the neighbor rows to the degree-budget bound
        ``m_if + m_is`` (the build trims trailing dead columns; streaming
        updates need that headroom back — DESIGN.md §11)."""
        r = m_full - self.nbrs.shape[1]
        if r <= 0:
            return self
        return self.replace(
            nbrs=jnp.pad(self.nbrs, ((0, 0), (0, r)), constant_values=-1),
            status=jnp.pad(self.status, ((0, 0), (0, r))),
        )

    def grow(self, need: int, m_full: int) -> "IndexStore":
        """Capacity-doubling growth: a store with materialized masks, rows
        widened to ``m_full``, and ≥ ``need`` free slots.  Virgin slots get
        inverted intervals ``[2, -2]`` (no predicate ever matches), ``-1``
        neighbor rows, zero plane codes, and ``free=True``."""
        from repro.kernels.beam_merge import next_pow2

        alive, free = self.masks()
        out = self.widen_rows(m_full).replace(alive=alive, free=free)
        cap = self.capacity
        n_free = int(jnp.sum(free))
        if n_free >= need:
            return out
        new_cap = max(2 * cap, next_pow2(cap + need - n_free))
        r = new_cap - cap
        pad_plane = lambda p: None if p is None else dataclasses.replace(
            p, data=jnp.pad(p.data, ((0, r), (0, 0)))
        )
        dead_iv = jnp.broadcast_to(
            jnp.asarray([2.0, -2.0], self.intervals.dtype), (r, 2)
        )
        return out.replace(
            entry=None,  # capacity growth invalidates it; insert rebuilds
            plane=pad_plane(out.plane),
            rerank=pad_plane(out.rerank),
            intervals=jnp.concatenate([out.intervals, dead_iv]),
            nbrs=jnp.pad(out.nbrs, ((0, r), (0, 0)), constant_values=-1),
            status=jnp.pad(out.status, ((0, r), (0, 0))),
            alive=jnp.pad(alive, (0, r)),
            free=jnp.pad(free, (0, r), constant_values=True),
        )

    # -------------------------------------------------------------- stats
    def memory_bytes(self) -> dict:
        """Per-component byte counts (the memory-footprint table's source)."""
        ent = self.entry
        out = {
            "plane": self.plane.memory_bytes(),
            "rerank": 0 if self.rerank is None else self.rerank.memory_bytes(),
            "graph": int(
                self.nbrs.size * self.nbrs.dtype.itemsize
                + self.status.size * self.status.dtype.itemsize
            ),
            "intervals": int(
                self.intervals.size * self.intervals.dtype.itemsize
            ),
            "entry": 0 if ent is None else int(
                sum(a.size * a.dtype.itemsize for a in ent)
            ),
            "masks": (0 if self.alive is None else self.capacity)
            + (0 if self.free is None else self.capacity),
        }
        out["total"] = sum(out.values())
        return out


def make_store(
    x,
    intervals,
    nbrs,
    status,
    *,
    dtype: str = "f32",
    rerank: bool = False,
    qparams=None,
    pq_m: int | None = None,
    entry: EntryIndex | None = None,
    build_entry: bool = True,
    alive: jnp.ndarray | None = None,
    free: jnp.ndarray | None = None,
) -> IndexStore:
    """Assemble an :class:`IndexStore` from f32 vectors + graph arrays.

    ``dtype`` selects the scan plane; ``rerank=True`` attaches the exact
    f32 plane for final-top-k re-scoring.  ``build_entry=False`` leaves
    ``entry=None`` (per-shard stores build theirs inside ``shard_map``).
    """
    x = jnp.asarray(x)
    intervals = jnp.asarray(intervals)
    if entry is None and build_entry:
        entry = build_entry_index(intervals, node_mask=alive)
    return IndexStore(
        plane=VectorPlane.encode(x, dtype, qparams, pq_m=pq_m),
        rerank=VectorPlane.encode(x, "f32") if rerank else None,
        intervals=intervals,
        nbrs=jnp.asarray(nbrs),
        status=jnp.asarray(status),
        entry=entry,
        alive=alive,
        free=free,
    )
