"""UGIndex — the user-facing unified interval-aware index (paper §4).

One physical graph + per-edge semantic bitmask answers IFANN / ISANN /
RFANN / RSANN queries (paper §2.1).  RF datasets store scalars as point
intervals; RS queries pass point query intervals — both reductions are
exact (§2.1).

Since DESIGN.md §12 the index is a thin host-side handle around one
:class:`~repro.core.store.IndexStore` pytree — the store is what every
layer (search, updates, serving, sharding, checkpointing) shares, and it
is held *by reference* everywhere (attaching an index to a ServeEngine
copies nothing).  The legacy array views (``x``/``intervals``/``graph``/
``entry``/``alive``/``free``) are properties over the store's buffers.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import intervals as iv
from repro.core.build import UGConfig, build_ug
from repro.core.entry import EntryIndex, build_entry_index
from repro.core.exact import DenseGraph
from repro.core.search import SearchResult, brute_force
from repro.core.search import search as core_search
from repro.core.search import search_mixed as core_search_mixed
from repro.core.store import IndexStore, VectorPlane, make_store


@dataclasses.dataclass
class UGIndex:
    """Unified graph index: one :class:`IndexStore` + build config.

    Store arrays are sized to ``capacity`` slots; ``alive`` marks the live
    nodes and ``free`` the slots the streaming allocator may hand out again
    (DESIGN.md §11).  A freshly built or loaded static index leaves both
    ``None`` (all slots live, none free) and pays zero masking cost.
    """

    store: IndexStore
    config: UGConfig
    build_seconds: float = 0.0

    # --------------------------------------------------------- store views
    @property
    def x(self) -> jnp.ndarray:
        """f32 view of the vectors: the exact rerank plane when present,
        else the decoded scan plane (identity — same buffer — for f32)."""
        return self.store.vectors_f32()

    @property
    def intervals(self) -> jnp.ndarray:
        return self.store.intervals

    @property
    def graph(self) -> DenseGraph:
        return self.store.graph

    @property
    def entry(self) -> EntryIndex:
        return self.store.entry

    @property
    def alive(self) -> jnp.ndarray | None:
        return self.store.alive

    @property
    def free(self) -> jnp.ndarray | None:
        return self.store.free

    @property
    def dtype(self) -> str:
        """Scan-plane tag: ``f32`` | ``bf16`` | ``int8`` | ``pq``."""
        return self.store.plane.tag

    def with_store(self, store: IndexStore) -> "UGIndex":
        return dataclasses.replace(self, store=store)

    # ------------------------------------------------------------------ build
    @classmethod
    def build(
        cls,
        x,
        intervals,
        config: UGConfig = UGConfig(),
        seed: int = 0,
        progress=None,
        *,
        dtype: str = "f32",
        rerank: bool | None = None,
    ) -> "UGIndex":
        """Alg. 1–3 build + plane encoding.

        The graph is always constructed from the f32 vectors; ``dtype``
        selects the *scan plane* the serving path scores against, and
        ``rerank`` attaches the exact f32 plane for final-top-k re-scoring
        (default: on for ``int8``/``pq``, off otherwise)."""
        x = jnp.asarray(x)
        intervals = jnp.asarray(intervals)
        t0 = time.perf_counter()
        graph = build_ug(jax.random.key(seed), x, intervals, config, progress)
        jax.block_until_ready(graph.nbrs)
        dt = time.perf_counter() - t0
        if rerank is None:
            rerank = dtype in ("int8", "pq")
        store = make_store(
            x, intervals, graph.nbrs, graph.status, dtype=dtype, rerank=rerank,
        )
        return cls(store, config, dt)

    def with_dtype(
        self, dtype: str, *, rerank: bool | None = None, pq_m: int | None = None
    ) -> "UGIndex":
        """Re-encode the vector planes (same graph, same ids): the
        cross-dtype parity harness — search quality of a ``bf16``/``int8``
        plane is measured against the f32 plane *on the identical graph*.
        ``pq_m`` is the pq subspace count (default ``default_pq_m``)."""
        if rerank is None:
            rerank = dtype in ("int8", "pq")
        x = self.store.vectors_f32()
        store = self.store.replace(
            plane=VectorPlane.encode(x, dtype, pq_m=pq_m),
            rerank=VectorPlane.encode(x, "f32") if rerank else None,
        )
        return self.with_store(store)

    # ----------------------------------------------------------------- search
    def search(
        self,
        q_v,
        q_int,
        *,
        sem: iv.Semantics = iv.Semantics.IF,
        ef: int = 64,
        k: int = 10,
        max_steps: int = 0,
        backend: str | None = None,
        width: int = 4,
    ) -> SearchResult:
        """Alg. 5 + Alg. 4.  ``backend``/``width`` select the search pipeline
        (fused multi-expansion by default; see core/search.py)."""
        return core_search(
            self.store, jnp.asarray(q_v), jnp.asarray(q_int),
            sem=sem, ef=ef, k=k, max_steps=max_steps,
            backend=backend, width=width,
        )

    def search_mixed(
        self,
        q_v,
        q_int,
        sem_flags,
        *,
        ef: int = 64,
        k: int = 10,
        max_steps: int = 0,
        backend: str | None = None,
        width: int = 4,
    ) -> SearchResult:
        """Alg. 5 + Alg. 4 for a batch whose queries each carry their own
        semantics — one compiled program serves interleaved IF/IS/RF/RS
        traffic (DESIGN.md §10).  ``sem_flags`` accepts a per-query sequence
        of :class:`Semantics`, a flag array, or a single ``Semantics``."""
        return core_search_mixed(
            self.store, jnp.asarray(q_v), jnp.asarray(q_int), sem_flags,
            ef=ef, k=k, max_steps=max_steps, backend=backend, width=width,
        )

    def ground_truth(self, q_v, q_int, *, sem: iv.Semantics, k: int) -> SearchResult:
        """Exact predicate-filtered top-k over the best-precision vectors
        (the rerank plane when present, else the decoded scan plane)."""
        return brute_force(
            self.store.vectors_f32(), self.intervals,
            jnp.asarray(q_v), jnp.asarray(q_int),
            sem=sem, k=k, alive=self.alive,
        )

    # ---------------------------------------------------------------- updates
    def insert(self, new_x, new_intervals, **kw) -> "UGIndex":
        """Batched streaming insert (DESIGN.md §11); returns a new UGIndex."""
        from repro.core.updates import insert_batch

        return insert_batch(self, new_x, new_intervals, **kw)

    def delete(self, ids, **kw) -> "UGIndex":
        """Batched tombstone delete + iterative repair; returns a new UGIndex."""
        from repro.core.updates import delete_batch

        return delete_batch(self, ids, **kw)

    def compact(self) -> "UGIndex":
        """Physically drop dead slots and remap the graph (DESIGN.md §11)."""
        from repro.core.updates import compact

        return compact(self)

    # ------------------------------------------------------------------ stats
    @property
    def capacity(self) -> int:
        """Allocated slots (live + tombstoned + free)."""
        return self.store.capacity

    @property
    def n(self) -> int:
        """Live node count (== capacity for a static index)."""
        if self.alive is None:
            return self.store.capacity
        return int(jnp.sum(self.alive))

    def memory_bytes(self) -> int:
        """Graph + entry + allocator bytes (the index *overhead* the paper's
        memory tables report; vector planes via :meth:`vector_memory_bytes`)."""
        m = self.store.memory_bytes()
        return int(m["graph"] + m["entry"] + m["masks"])

    def vector_memory_bytes(self) -> dict:
        """Per-plane vector bytes (scan plane, rerank plane, per-vector).

        Bytes/vec amortizes over the *live* count, not capacity — after
        ``grow()`` doubles capacity the figure must not silently halve."""
        m = self.store.memory_bytes()
        return {
            "plane": m["plane"],
            "rerank": m["rerank"],
            "plane_bytes_per_vector": self.store.plane.bytes_per_vector(self.n),
        }

    def degree_stats(self) -> dict:
        g = self.graph
        d_if = np.asarray(g.degree(iv.FLAG_IF))
        d_is = np.asarray(g.degree(iv.FLAG_IS))
        if self.alive is not None:  # stats over live rows only
            live = np.asarray(self.alive)
            d_if = d_if[live]
            d_is = d_is[live]
        return {
            "mean_if": float(d_if.mean()),
            "mean_is": float(d_is.mean()),
            "max_if": int(d_if.max()),
            "max_is": int(d_is.max()),
            "edges": int((np.asarray(g.nbrs) >= 0).sum()),
        }

    # ------------------------------------------------------------------- io
    def save(self, path: str | pathlib.Path) -> None:
        path = pathlib.Path(path)
        path.mkdir(parents=True, exist_ok=True)
        st = self.store
        x_np = np.asarray(st.plane.data)
        if st.plane.tag == "bf16":
            # numpy serializes ml_dtypes bfloat16 as raw void ('|V2') and
            # cannot read it back: store the codes as a uint16 bit view
            # (load re-casts keyed on the saved dtype tag).
            x_np = x_np.view(np.uint16)
        arrays = dict(
            x=x_np,
            intervals=np.asarray(st.intervals),
            nbrs=np.asarray(st.nbrs),
            status=np.asarray(st.status),
        )
        if st.plane.scale is not None:
            arrays["x_scale"] = np.asarray(st.plane.scale)
            arrays["x_zero"] = np.asarray(st.plane.zero)
        if st.plane.codebooks is not None:
            arrays["x_codebooks"] = np.asarray(st.plane.codebooks)
        if st.rerank is not None:
            arrays["rerank"] = np.asarray(st.rerank.data)
        if st.alive is not None:
            arrays["alive"] = np.asarray(st.alive)
            arrays["free"] = (
                np.zeros(arrays["alive"].shape, bool) if st.free is None
                else np.asarray(st.free)
            )
        np.savez_compressed(path / "index.npz", **arrays)
        meta = dataclasses.asdict(self.config)
        meta["build_seconds"] = self.build_seconds
        meta["dtype"] = st.plane.tag
        (path / "meta.json").write_text(json.dumps(meta, indent=2))

    @classmethod
    def load(cls, path: str | pathlib.Path) -> "UGIndex":
        path = pathlib.Path(path)
        blob = np.load(path / "index.npz")
        meta = json.loads((path / "meta.json").read_text())
        build_seconds = meta.pop("build_seconds", 0.0)
        tag = meta.pop("dtype", "f32")
        cfg = UGConfig(**meta)
        intervals = jnp.asarray(blob["intervals"])
        alive = jnp.asarray(blob["alive"]) if "alive" in blob.files else None
        free = jnp.asarray(blob["free"]) if "free" in blob.files else None
        x_np = blob["x"]
        if tag == "bf16":  # stored as a uint16 bit view (see save)
            x_np = jnp.asarray(x_np).view(jnp.bfloat16)
        plane = VectorPlane(
            tag, jnp.asarray(x_np),
            jnp.asarray(blob["x_scale"]) if "x_scale" in blob.files else None,
            jnp.asarray(blob["x_zero"]) if "x_zero" in blob.files else None,
            jnp.asarray(blob["x_codebooks"])
            if "x_codebooks" in blob.files else None,
        )
        rerank = (
            VectorPlane("f32", jnp.asarray(blob["rerank"]))
            if "rerank" in blob.files else None
        )
        store = IndexStore(
            plane=plane, rerank=rerank, intervals=intervals,
            nbrs=jnp.asarray(blob["nbrs"]), status=jnp.asarray(blob["status"]),
            entry=build_entry_index(intervals, node_mask=alive),
            alive=alive, free=free,
        )
        return cls(store, cfg, build_seconds)


def recall(result: SearchResult, truth: SearchResult) -> float:
    """recall@k as in the paper §5.1 (set overlap with brute-force truth)."""
    r = np.asarray(result.ids)
    t = np.asarray(truth.ids)
    hits = 0
    denom = 0
    for i in range(r.shape[0]):
        tset = set(int(v) for v in t[i] if v >= 0)
        if not tset:
            continue
        rset = set(int(v) for v in r[i] if v >= 0)
        hits += len(tset & rset)
        denom += len(tset)
    return hits / max(denom, 1)
