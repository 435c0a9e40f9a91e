"""Multi-device tests, run in a subprocess with 8 fake CPU devices (the
device count must be fixed before jax initializes, so these can't share the
main pytest process which other tests run single-device)."""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def run_sub(code: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_search_and_ring_knn():
    run_sub(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import intervals as iv, brute_force, recall
from repro.core.build import UGConfig
from repro.core.search import SearchResult
from repro.core.sharded import (build_sharded_index_host, shard_index,
                                make_sharded_search_fn, make_ring_knn_fn)
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
n, d = 1200, 12
x = np.asarray(jax.random.normal(k1, (n, d)))
ints = np.asarray(iv.sample_uniform_intervals(k2, n))
cfg = UGConfig(ef_spatial=16, ef_attribute=32, max_edges_if=16, max_edges_is=16,
               iterations=2, repair_width=8, exact_spatial=True, block=512)
xs, its, nbs, sts, gid = build_sharded_index_host(x, ints, 4, cfg)
sidx = shard_index(mesh, ("data",), xs, its, nbs, sts, gid)
nq = 16
qv = jax.random.normal(k3, (nq, d))
c = jax.random.uniform(k4, (nq, 1))
qi = jnp.concatenate([jnp.maximum(c-0.3,0), jnp.minimum(c+0.3,1)], axis=1)
fn = make_sharded_search_fn(mesh, index_axes=("data",), sem=iv.Semantics.IF, ef=48, k=10)
ids, dist = fn(sidx, qv, qi)
gt = brute_force(jnp.asarray(x), jnp.asarray(ints), qv, qi, sem=iv.Semantics.IF, k=10)
r = recall(SearchResult(ids, dist, None), gt)
assert r >= 0.9, r

# mixed runtime-semantics sharded search: one program, per-query flags;
# rows must equal the corresponding static-semantics program bit-for-bit
fnm = make_sharded_search_fn(mesh, index_axes=("data",), sem=iv.Semantics.IF,
                             ef=48, k=10, mixed=True)
flags = jnp.asarray([iv.FLAG_IF, iv.FLAG_IS] * (nq // 2), jnp.int32)
ids_m, dist_m = fnm(sidx, qv, qi, flags)
fn_is = make_sharded_search_fn(mesh, index_axes=("data",), sem=iv.Semantics.IS, ef=48, k=10)
ids_is, dist_is = fn_is(sidx, qv, qi)
f_np = np.asarray(flags)
for sel, ref_ids, ref_d in ((f_np == iv.FLAG_IF, ids, dist),
                            (f_np == iv.FLAG_IS, ids_is, dist_is)):
    assert np.array_equal(np.asarray(ids_m)[sel], np.asarray(ref_ids)[sel])
    assert np.array_equal(np.asarray(dist_m)[sel], np.asarray(ref_d)[sel])

ring = make_ring_knn_fn(mesh, axis="data", k=8)
row = NamedSharding(mesh, P(("data",)))
ri, rd = ring(jax.device_put(xs, row), jax.device_put(gid, row))
ri_np = np.asarray(ri)
gid_np = np.asarray(gid)
for local_row in (0, 7, 131):
    g = gid_np[local_row]
    if g < 0: continue
    dall = ((x - x[g])**2).sum(1); dall[g] = np.inf
    assert set(ri_np[local_row].tolist()) == set(np.argsort(dall)[:8].tolist())
print("sharded search + ring knn OK", r)
"""
    )


def test_ep_moe_and_compression():
    run_sub(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models import ModelConfig, shard_ctx
from repro.models import moe as moe_lib
from repro.models.common import ParamBuilder
from repro.launch.mesh import make_mesh
from repro.distributed import compressed_psum, init_ef

# EP MoE == local MoE
cfg = ModelConfig(family="decoder", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
                  d_ff=32, vocab=32, moe=True, n_experts=8, top_k=2, moe_d_ff=32,
                  n_shared_experts=1, capacity_factor=16.0, dtype=jnp.float32)
b = ParamBuilder(cfg, "init", key=jax.random.key(0))
p = moe_lib.build_moe_params(cfg, b, prefix_layers=False)
x = jax.random.normal(jax.random.key(7), (4, 8, 16))
y0, a0 = moe_lib._moe_ffn_local(cfg, p, x)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
with shard_ctx.use_mesh(mesh):
    y1, a1 = jax.jit(lambda pp, xx: moe_lib.moe_ffn(cfg, pp, xx))(p, x)
assert float(jnp.max(jnp.abs(y0 - y1))) < 1e-4
assert abs(float(a0) - float(a1)) < 1e-6

# compressed psum with error feedback ~= plain psum
mesh2 = make_mesh((8,), ("data",))
g = {"w": jax.random.normal(jax.random.key(1), (8, 512))}
ef = init_ef({"w": g["w"][0]})
def local(gw):
    grads = {"w": gw[0]}
    mean_g, new_ef = compressed_psum(grads, init_ef(grads), "data")
    return mean_g["w"][None]
fn = jax.shard_map(local, mesh=mesh2, in_specs=(P("data", None),),
               out_specs=P("data", None), check_vma=False)
out = fn(g["w"][:, None, :].reshape(8, 1, 512))
expect = jnp.mean(g["w"], axis=0)
err = float(jnp.max(jnp.abs(out[0] - expect)))
rel = err / float(jnp.max(jnp.abs(expect)))
assert rel < 0.05, rel   # int8 quantization noise bound
print("EP MoE + compression OK", rel)
"""
    )


def test_ring_collectives():
    run_sub(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed import ring_all_gather, ring_reduce_scatter
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.key(0), (8, 4))

def ag(xl):
    size, blocks = ring_all_gather(xl[0], "data")
    return blocks[None]
out = jax.shard_map(ag, mesh=mesh, in_specs=(P("data", None),),
                out_specs=P("data", None, None), check_vma=False)(x[:, None, :].reshape(8,1,4))
# rank r's ring order starts at its own shard going backwards around the ring
me0 = np.asarray(out[0]).reshape(8, 4)
assert np.allclose(me0[0], np.asarray(x[0]))
assert set(map(tuple, me0.round(4).tolist())) == set(map(tuple, np.asarray(x).round(4).tolist()))

y = jax.random.normal(jax.random.key(1), (8, 8, 4))  # per rank: (8 chunks, 4)
def rs(yl):
    return ring_reduce_scatter(yl[0], "data")[None]
out2 = jax.shard_map(rs, mesh=mesh, in_specs=(P("data", None, None),),
                 out_specs=P("data", None), check_vma=False)(y)
expect = jnp.sum(y, axis=0)  # sum over ranks, chunk r to rank r
np.testing.assert_allclose(np.asarray(out2), np.asarray(expect), atol=1e-5)
print("ring collectives OK")
"""
    )


def test_elastic_restore_across_meshes(tmp_path):
    run_sub(
        f"""
import jax, jax.numpy as jnp, numpy as np
from repro.models import ModelConfig, get_model
from repro.train import AdamWConfig, optim
from repro.ckpt import save
from repro.ft import resume
from repro.launch.mesh import make_mesh

cfg = ModelConfig(family="decoder", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
                  d_ff=64, vocab=64, dtype=jnp.float32)
model = get_model(cfg)
params = model.init(jax.random.key(0))
ocfg = AdamWConfig()
ostate = optim.init(ocfg, params)
save(r'{tmp_path}', 7, params, ostate, data_cursor=7)

# restore onto an 8-device mesh (checkpoint was written single-device)
mesh = make_mesh((4, 2), ("data", "model"))
rp, ro, meta = resume(r'{tmp_path}', model, ostate, mesh)
assert meta["data_cursor"] == 7
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rp)):
    assert np.allclose(np.asarray(a), np.asarray(b))
# leaves are actually device-sharded now
shardings = {{str(l.sharding) for l in jax.tree.leaves(rp)}}
assert any("model" in s or "data" in s for s in shardings)
print("elastic restore OK")
"""
    )


def test_serve_fleet_monitor_on_sharded_index():
    """Straggler probing + elastic replica planning over a real sharded
    store (DESIGN.md §13): per-shard probe callables reproduce the
    shard_map-local search (their merged top-k covers the global answer),
    a degrading shard is flagged, and the degraded replica plan sheds that
    shard's device group."""
    run_sub(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.core import intervals as iv
from repro.core.build import UGConfig
from repro.core.sharded import (build_sharded_index_host, shard_index,
                                make_sharded_search_fn, make_shard_probe_fns)
from repro.launch.mesh import make_mesh
from repro.serve import FleetServeMonitor
from repro.ft.straggler import StragglerConfig

mesh = make_mesh((4, 2), ("data", "model"))
k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
n, d, S = 1200, 12, 4
x = np.asarray(jax.random.normal(k1, (n, d)))
ints = np.asarray(iv.sample_uniform_intervals(k2, n))
cfg = UGConfig(ef_spatial=16, ef_attribute=32, max_edges_if=16, max_edges_is=16,
               iterations=2, repair_width=8, exact_spatial=True, block=512)
xs, its, nbs, sts, gid = build_sharded_index_host(x, ints, S, cfg)
sidx = shard_index(mesh, ("data",), xs, its, nbs, sts, gid)

nq, k = 8, 10
qv = jax.random.normal(k3, (nq, d))
c = jax.random.uniform(k4, (nq, 1))
qi = jnp.concatenate([jnp.maximum(c-0.3,0), jnp.minimum(c+0.3,1)], axis=1)
flags = jnp.asarray([iv.FLAG_IF if i % 2 else iv.FLAG_IS for i in range(nq)],
                    jnp.int32)

# probe fns run the same per-shard program the shard_map step runs: the
# union of per-shard top-k must cover the global sharded answer
probe_fns = make_shard_probe_fns(sidx, S, ef=48, k=k)
per_shard = [fn(qv, qi, flags) for fn in probe_fns]
fn_g = make_sharded_search_fn(mesh, index_axes=("data",), sem=iv.Semantics.IF,
                              ef=48, k=k, mixed=True)
gids, gdist = fn_g(sidx, qv, qi, flags)
union_ids = np.concatenate([np.asarray(p[0]) for p in per_shard], axis=1)
for q in range(nq):
    got = set(np.asarray(gids)[q].tolist()) - {-1}
    cover = set(union_ids[q].tolist())
    assert got <= cover, (q, got - cover)

# fleet health: warm the timers with real probe timings, then shard 2
# degrades 20x — it must be flagged and the degraded plan must shed its
# device group while keeping the shard axis intact
scfg = StragglerConfig()
fm = FleetServeMonitor(n_shards=S, n_devices=8, cfg=scfg)
for _ in range(scfg.warmup + scfg.baseline_min + scfg.recent):
    times = fm.probe(probe_fns, qv, qi, flags)
    assert len(times) == S and all(t > 0 for t in times)
base = float(np.median([np.median(t._recent()) for t in fm.fleet.timers]))
for _ in range(2 * scfg.recent):
    for s in range(S):
        fm.record(s, 20.0 * base if s == 2 else base)
rep = fm.report()
assert rep["stragglers"] == [2], rep["stragglers"]
assert rep["recommendations"].get(2) == "checkpoint_now"
assert rep["plan"].mesh_shape == (2, S)
assert rep["degraded_plan"] is not None
assert rep["degraded_plan"].mesh_shape == (1, S)
assert rep["degraded_plan"].dropped_pods == 2
print("fleet monitor OK")
"""
    )
