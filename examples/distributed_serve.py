"""Distributed (row-sharded) index serving on 8 simulated devices.

The corpus is split over a (data=4, model=2) mesh; each shard runs local
interval-aware beam search; per-shard top-k merge via all_gather — the same
shard_map program the 512-chip dry-run lowers (launch/dryrun.py --index-cell).

Run:  PYTHONPATH=src python examples/distributed_serve.py
(sets XLA_FLAGS itself; run in a fresh process)
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Semantics, UGConfig, brute_force, recall
from repro.core import intervals as iv
from repro.core.search import SearchResult
from repro.core.sharded import (build_sharded_store, make_ring_knn_fn,
                                make_sharded_search_fn)
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
n, d = 4000, 24
x = np.asarray(jax.random.normal(k1, (n, d)))
ints = np.asarray(iv.sample_uniform_intervals(k2, n))

cfg = UGConfig(ef_spatial=24, ef_attribute=48, max_edges_if=24, max_edges_is=24,
               iterations=2, repair_width=8, exact_spatial=True, block=1024)
t0 = time.perf_counter()
# On-device sharded build (DESIGN.md §12): one shard_map program constructs
# all 4 shard-local UGs in parallel — exact KNN over each shard's rows +
# shard-local attribute orders + the same jitted prune/repair iterations
# build_ug runs.
sidx = build_sharded_store(mesh, x, ints, cfg, index_axes=("data",))
jax.block_until_ready(sidx.store.nbrs)
print(f"built 4 shard-local UGs on-device in {time.perf_counter()-t0:.1f}s "
      "(heredity => shard-local graphs are sound)")

nq = 64
qv = jax.random.normal(k3, (nq, d))
c = jax.random.uniform(k4, (nq, 1))
qi = jnp.concatenate([jnp.maximum(c - .3, 0), jnp.minimum(c + .3, 1)], axis=1)

for sem in (Semantics.IF, Semantics.IS):
    fn = make_sharded_search_fn(mesh, index_axes=("data",), sem=sem, ef=64, k=10)
    ids, dist = fn(sidx, qv, qi)
    jax.block_until_ready(ids)
    t0 = time.perf_counter()
    ids, dist = fn(sidx, qv, qi)
    jax.block_until_ready(ids)
    dt = time.perf_counter() - t0
    gt = brute_force(jnp.asarray(x), jnp.asarray(ints), qv, qi, sem=sem, k=10)
    r = recall(SearchResult(ids, dist, None), gt)
    print(f"{sem.value}: recall@10 = {r:.3f}   QPS = {nq/dt:,.0f}")

# int8 scan plane + f32 rerank: 4x less per-vector scan traffic, same top-k
sidx8 = build_sharded_store(mesh, x, ints, cfg, index_axes=("data",),
                            dtype="int8", rerank=True)
fn8 = make_sharded_search_fn(mesh, index_axes=("data",), sem=Semantics.IF,
                             ef=64, k=10, plane_tag="int8", has_rerank=True)
ids8, dist8 = fn8(sidx8, qv, qi)
gt = brute_force(jnp.asarray(x), jnp.asarray(ints), qv, qi, sem=Semantics.IF, k=10)
print(f"int8+rerank IF recall@10 = "
      f"{recall(SearchResult(ids8, dist8, None), gt):.3f} "
      f"({sidx8.store.plane.bytes_per_vector():.1f} scan B/vec)")

# bonus: the ring-streamed exact KNN builder (collective_permute pipeline)
ring = make_ring_knn_fn(mesh, axis="data", k=8)
ri, _ = ring(sidx.store.plane.data, sidx.global_ids)
print(f"ring-streamed exact KNN over {n} rows: done, shape {ri.shape}")
