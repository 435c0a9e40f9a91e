"""Compile the main path for a described TPU v5e, without the chip.

Interpret mode (what every other test runs on the CPU) accepts kernels the
chip's compiler refuses: unaligned block shapes, single-row slices of tiled
HBM arrays, lane reversals, dynamic slices of loaded values.  These tests
lower and compile the Pallas kernels of the build and search path with
Mosaic at the shapes a 1M × 96 deployment passes them (and the f32 scorer
at d = 960), plus one whole ``search_mixed`` program at n = 1M.  Nothing
runs: a compile that passes says nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and the worker that runs this
file keeps it until it exits.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# The shapes chip_smoke.py serves: 64 + 64 edges, frontier width 4, ef=512.
N, D, B, C = 1_000_000, 96, 256, 512   # corpus, width, search batch, W·M
EF, K, M = 512, 10, 128                # beam, top-k, graph width
PQ_M = 12                              # default_pq_m(96)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """Shape factory placing every argument on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return compiled


@pytest.mark.parametrize("d", [D, 960])
def test_expand_score_compiles(sds, d):
    from repro.kernels.expand_score import expand_score

    _compile(expand_score, sds((N, d), jnp.float32), sds((B, C), jnp.int32),
             sds((B, d), jnp.float32))


def test_expand_score_q_compiles(sds):
    from repro.kernels.expand_score import expand_score_q

    _compile(expand_score_q, sds((N, D), jnp.int8), sds((D,), jnp.float32),
             sds((D,), jnp.float32), sds((B, C), jnp.int32),
             sds((B, D), jnp.float32))


@pytest.mark.parametrize("m", [PQ_M, 48])   # default_pq_m(96); chip_smoke's
def test_expand_score_pq_compiles(sds, m):
    from repro.kernels.expand_score import expand_score_pq

    _compile(expand_score_pq, sds((N, m), jnp.uint8),
             sds((m, 256, D // m), jnp.float32), sds((B, C), jnp.int32),
             sds((B, D), jnp.float32))


@pytest.mark.parametrize("L", [C, 4])   # expansion step; entry seeding (W)
def test_beam_merge_compiles(sds, L):
    from repro.kernels.beam_merge import beam_merge

    _compile(beam_merge, sds((B, EF), jnp.float32), sds((B, EF), jnp.int32),
             sds((B, L), jnp.float32), sds((B, L), jnp.int32))


# chip_smoke.py's build (64 + 64 edges, ef_attribute=64): iteration 0
# (64 + 64), repair rounds (128 + 16), insert (2·64 + 4·17), delete repair
# pool (4·128)
@pytest.mark.parametrize("pool", [128, 144, 196, 512])
def test_prune_sweep_compiles(sds, pool):
    from repro.kernels.prune_sweep import prune_sweep

    blk = 1024  # UGConfig.block
    fn = functools.partial(prune_sweep, m_if=64, m_is=64, alpha=1.0, unified=True)
    _compile(fn, sds((blk, 2), jnp.float32), sds((blk, pool, D), jnp.float32),
             sds((blk, pool, 2), jnp.float32), sds((blk, pool), jnp.float32),
             sds((blk, pool), jnp.bool_), sds((blk, pool), jnp.bool_))


def test_search_mixed_compiles(sds, monkeypatch):
    """One whole mixed-semantics search program at n = 1M on the f32 plane,
    with ``backend="pallas"`` passed explicitly.  On this host the kernels
    would trace in interpret mode, so the test steers ``ops.on_cpu`` to the
    chip's answer for the duration of the trace."""
    from repro.core.entry import EntryIndex
    from repro.core.search import search_mixed
    from repro.core.store import IndexStore, VectorPlane
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_cpu", lambda: False)
    f32, i32 = jnp.float32, jnp.int32
    entry = EntryIndex(*(sds((N,), dt) for dt in (i32, f32, f32, i32, f32, i32)))
    store = IndexStore(
        plane=VectorPlane("f32", sds((N, D), f32)), rerank=None,
        intervals=sds((N, 2), f32), nbrs=sds((N, M), i32),
        status=sds((N, M), jnp.uint8), entry=entry,
    )
    fn = functools.partial(search_mixed, ef=EF, k=K, backend="pallas")
    compiled = _compile(fn, store, sds((B, D), f32), sds((B, 2), f32),
                        sds((B,), i32))
    # expand_score (seed + step), beam_merge (seed + step): all Mosaic
    assert compiled.as_text().count("tpu_custom_call") >= 4
