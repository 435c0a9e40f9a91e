"""Fused expand-score kernel for the beam-search hot loop (Alg. 4 inner).

Every fused search step scores the ``C = W·M`` neighbor candidates of the
``W`` expanded frontier nodes against the query.  The pre-fusion path
materialized the full ``(B, C, d)`` candidate gather in HBM and ran one
batched matmul over it — at serving shapes (``B`` in the thousands,
``C = 128–512``, ``d`` up to 1536) that gather is the dominant per-step HBM
traffic of the query side, the exact quadratic-intermediate pattern the
build sweep already eliminated (DESIGN.md §9 → §10).

Three backends, dispatched via :func:`repro.kernels.ops.expand_score`:

* ``pallas`` — tile gather driven by the ids: the ``(B, C)`` candidate ids
  sit in SMEM, and for each live id the kernel DMAs the HBM tile holding
  that row into VMEM (double-buffered, one query's chunk of candidates
  while the previous chunk is scored), picks the row out of the tile and
  scores it.  The ``(B, C, d)`` tensor never exists.
* ``xla`` — the interpretable CPU-CI twin: a ``fori_loop`` over
  ``chunk``-wide candidate slices, peak intermediate ``(B, chunk, d)``.
* ``legacy`` — the pre-fusion baseline (full gather + matmul identity),
  kept for A/B profiling in ``bench_mixed_workload``.

Bit-identity contract (same reasoning as the prune sweep, DESIGN.md §9):
the fused backends compute each distance as an *elementwise*
square-difference sum over the feature axis, which is bitwise invariant
under any row blocking — per-row results do not depend on ``B``, ``C``,
the ``chunk`` width, or the batch composition.  That invariance is what
lets one mixed-semantics batch return bit-identical distances to four
per-semantics batches (DESIGN.md §10).  ``legacy`` uses the matmul
identity ``‖x‖² + ‖q‖² − 2·x·q`` whose reduction order is shape-dependent,
so it is only ever compared with ``allclose``.

Also here: the sort-based per-row first-occurrence dedup that replaces the
``O(C²)`` pairwise mask the search loop used to build twice per step (sort
by id, mask equal-adjacent, unsort — ``O(C log C)``, no ``(B, C, C)``
intermediate).  This module absorbs the former ``kernels/gather_dist.py``
(:func:`gather_sq_dist` is the same kernel, kept under its historical name
for the kernel microbenches).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import compiler_params


# ------------------------------------------------------------------ pallas
# Shared machinery of the three row-gather kernels (f32/bf16, int8, pq).
#
# The grid walks the batch ``_TB`` query rows at a time; each grid step
# writes a ``(_TB, C)`` output block.  The plane stays in HBM
# (``memory_space=pl.ANY``) viewed as ``(n/R, R, w)`` tiles — ``R`` rows of
# one native ``(8, 128)`` 32-bit tile (16 rows for bf16, 32 for 8-bit codes)
# and ``w`` lanes padded to a multiple of 128 — because Mosaic DMAs whole
# tiles only: a single-row slice of a tiled HBM array is refused.  For each
# candidate the kernel DMAs the tile holding its row into a double-buffered
# VMEM chunk (``K`` candidates per chunk, driven by the ids in SMEM; masked
# ``-1`` ids issue no DMA), picks the row out of the tile with a masked
# sublane sum (exact: one value plus zeros), and scores the chunk.  No
# ``(B, C, d)`` array exists in HBM.
_TB = 8                  # query rows per grid step (f32 sublane tile)
_LANE = 128
_C_ALIGN = 128           # _TB * C must fill whole 1024-word SMEM tiles
_CHUNK_BYTES = 4 << 20   # one buffer slot of candidate tiles in VMEM
_VMEM_LIMIT = 48 << 20


def _tile_rows(dtype) -> int:
    """Rows of one native HBM tile: 8 for 32-bit, 16 for 16-bit, 32 for 8-bit."""
    return 32 // jnp.dtype(dtype).itemsize


def tile_view(a: jnp.ndarray) -> jnp.ndarray:
    """``(n, w)`` plane → ``(ceil(n/R), R, w_pad)`` HBM tile view, ``w_pad`` a
    multiple of 128.  A pure reshape when ``n % R == 0`` and ``w % 128 ==
    0``; otherwise one padded copy of the plane — the fused search builds it
    once per batch (:func:`repro.kernels.ops.plane_tiles`), not per step."""
    n, w = a.shape
    R = _tile_rows(a.dtype)
    n_p = -(-n // R) * R
    w_p = -(-w // _LANE) * _LANE
    if (n_p, w_p) != (n, w):
        a = jnp.pad(a, ((0, n_p - n), (0, w_p - w)))
    return a.reshape(n_p // R, R, w_p)


def _chunk_width(C: int, tile_bytes: int) -> int:
    """Candidates per DMA chunk: all ``C`` of a query when they fit the
    budget, else the largest lane-aligned divisor of ``C`` that does."""
    if C * tile_bytes <= _CHUNK_BYTES or C % _LANE:
        return C
    k = _LANE
    while C % (2 * k) == 0 and 2 * k * tile_bytes <= _CHUNK_BYTES:
        k *= 2
    return k


def _select_row(tile: jnp.ndarray, s) -> jnp.ndarray:
    """Row ``s`` of an ``(R, w)`` tile as ``(1, w)``: a masked sublane sum,
    exact (the row's values plus zeros)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(jnp.where(sub == s, tile, jnp.zeros_like(tile)),
                   axis=0, keepdims=True)


def _as_row(col: jnp.ndarray) -> jnp.ndarray:
    """``(K, 1)`` column → ``(1, K)`` lane row (one XLU transpose)."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], _LANE)))[0:1, :]


def _gather_tiles(ids_ref, src, buf, sem, *, tb, C, K, R, consume):
    """Stream the candidate tiles of ``tb`` query rows through ``buf``.

    ``ids_ref`` holds the ``tb·C`` row ids of this grid step (``-1`` =
    masked: no DMA).  Chunk ``j = b·(C/K) + p`` (query ``b``, candidates
    ``[p·K, (p+1)·K)``) lands in slot ``j % 2`` while chunk ``j - 1`` is
    consumed by ``consume(b, p, slot)``.  ``b`` is static; so is ``p`` when
    one chunk holds a query's candidates, else ``K`` is a multiple of 128
    and ``p`` a loop index.
    """
    per = C // K
    n_chunks = tb * per

    def each(j, op):
        slot = j % 2

        def f(k, carry):
            r = ids_ref[j * K + k]
            cp = pltpu.make_async_copy(
                src.at[jnp.maximum(r, 0) // R], buf.at[slot, k], sem.at[slot])
            pl.when(r >= 0)(lambda: op(cp))
            return carry

        jax.lax.fori_loop(0, K, f, 0)

    def chunk(b, p):
        j = b * per + p
        pl.when(j + 1 < n_chunks)(lambda: each(j + 1, lambda cp: cp.start()))
        each(j, lambda cp: cp.wait())
        consume(b, p, j % 2)

    each(0, lambda cp: cp.start())
    for b in range(tb):
        if per == 1:
            chunk(b, 0)
            continue

        def body(p, carry, b=b):
            chunk(b, p)
            return carry

        jax.lax.fori_loop(0, per, body, 0)


def _lanes(p, K: int):
    """Lane window of chunk ``p`` in the ``(_TB, C)`` output block."""
    if isinstance(p, int):
        return slice(p * K, (p + 1) * K)
    return pl.ds(pl.multiple_of(p * K, _LANE), K)


def _flat_ids(idx: jnp.ndarray, n: int, Bp: int, Cp: int) -> jnp.ndarray:
    """``(B, C)`` ids → flat ``(Bp·Cp,)`` int32, ``-1`` for masked and pad
    slots, valid ids clipped into the plane."""
    B, C = idx.shape
    ids = jnp.where(idx >= 0, jnp.minimum(idx, n - 1), -1).astype(jnp.int32)
    return jnp.pad(ids, ((0, Bp - B), (0, Cp - C)), constant_values=-1).reshape(-1)


def _padded_dims(B: int, C: int) -> tuple[int, int]:
    return -(-B // _TB) * _TB, -(-C // _C_ALIGN) * _C_ALIGN


def _row_gather_call(kernel, ids, row_inputs, tiles, *, Bp, Cp, K, extra_scratch,
                     name, interpret):
    """``pallas_call`` over ``Bp/_TB`` query tiles: ``ids`` in SMEM, the
    per-query ``row_inputs`` (leading axis ``Bp``) and broadcast
    ``(1, ·)`` params blocked in VMEM, the plane ``tiles`` left in HBM."""
    def block(a):
        if a.shape[0] == Bp:
            return pl.BlockSpec((_TB,) + a.shape[1:],
                                lambda i, nd=a.ndim: (i,) + (0,) * (nd - 1))
        return pl.BlockSpec(a.shape, lambda i, nd=a.ndim: (0,) * nd)

    return pl.pallas_call(
        kernel,
        grid=(Bp // _TB,),
        in_specs=[pl.BlockSpec((_TB * Cp,), lambda i: (i,),
                               memory_space=pltpu.SMEM)]
        + [block(a) for a in row_inputs]
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_TB, Cp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Cp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, K) + tiles.shape[1:], tiles.dtype),
                        *extra_scratch, pltpu.SemaphoreType.DMA((2,))],
        compiler_params=compiler_params(("arbitrary",),
                                        vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(ids, *row_inputs, tiles)


def _kernel_rows(ids_ref, q_ref, *refs, C, K, R, d, quantized):
    """Squared L2 of each query row against its gathered candidate rows:
    ``rows`` collects the ``K`` selected rows of a chunk, then one
    elementwise square-difference sum over the feature axis scores them —
    the same network as the XLA twins."""
    if quantized:
        s_ref, z_ref, x_hbm, o_ref, buf, rows, sem = refs
    else:
        x_hbm, o_ref, buf, rows, sem = refs

    def consume(b, p, slot):
        def pick(k, carry):
            t = buf[slot, k]
            t = t.astype(jnp.int32) if quantized else t.astype(jnp.float32)
            s = jnp.maximum(ids_ref[b * C + p * K + k], 0) % R
            rows[pl.ds(k, 1), :] = _select_row(t, s).astype(jnp.float32)
            return carry

        jax.lax.fori_loop(0, K, pick, 0)
        x = rows[:, :d]                                    # (K, d)
        if quantized:
            x = x * s_ref[...] + z_ref[...]                # dequant in-register
        diff = q_ref[b:b + 1, :].astype(jnp.float32) - x
        dist = jnp.sum(diff * diff, axis=-1, keepdims=True)   # (K, 1)
        o_ref[b:b + 1, _lanes(p, K)] = _as_row(dist)

    _gather_tiles(ids_ref, x_hbm, buf, sem, tb=_TB, C=C, K=K, R=R,
                  consume=consume)


def _rows_call(x, idx, q, tiles, qparams, interpret):
    B, C = idx.shape
    n, d = x.shape
    if tiles is None:
        tiles = tile_view(x)
    Bp, Cp = _padded_dims(B, C)
    R = tiles.shape[1]
    K = _chunk_width(Cp, R * tiles.shape[2] * tiles.dtype.itemsize)
    q = jnp.pad(q, ((0, Bp - B), (0, 0)))
    kernel = functools.partial(_kernel_rows, C=Cp, K=K, R=R, d=d,
                               quantized=qparams is not None)
    out = _row_gather_call(
        kernel, _flat_ids(idx, n, Bp, Cp), (q,) + tuple(qparams or ()), tiles,
        Bp=Bp, Cp=Cp, K=K,
        extra_scratch=[pltpu.VMEM((K, tiles.shape[2]), jnp.float32)],
        name="expand_score" if qparams is None else "expand_score_q",
        interpret=interpret,
    )[:B, :C]
    return jnp.where(idx >= 0, out, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expand_score(
    x: jnp.ndarray,     # (n, d) corpus (stays in HBM; tiles DMA'd on demand)
    idx: jnp.ndarray,   # (B, C) int32 candidate ids (-1 = masked/padding)
    q: jnp.ndarray,     # (B, d) queries
    *,
    interpret: bool = False,
    tiles: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Squared L2 between ``q[b]`` and ``x[idx[b, c]]``; ``+inf`` where
    ``idx < 0``.  One HBM tile DMA per live candidate, scheduled from the
    ids in SMEM — no ``(B, C, d)`` intermediate.  ``tiles`` is
    :func:`tile_view` of ``x`` when the caller already built it."""
    return _rows_call(x, idx, q, tiles, None, interpret)


# Historical name from the absorbed kernels/gather_dist.py (microbenches,
# kernel sweep tests): same kernel, same semantics.
gather_sq_dist = expand_score


@functools.partial(jax.jit, static_argnames=("interpret",))
def expand_score_q(
    x: jnp.ndarray,      # (n, d) int8 quantized corpus plane
    scale: jnp.ndarray,  # (d,) f32 per-dimension scale
    zero: jnp.ndarray,   # (d,) f32 per-dimension zero point
    idx: jnp.ndarray,    # (B, C) int32 candidate ids (-1 = masked/padding)
    q: jnp.ndarray,      # (B, d) queries
    *,
    interpret: bool = False,
    tiles: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Quantized-plane :func:`expand_score`: the gathered rows are int8 codes
    dequantized in-register (``x·scale + zero``) before the square-diff sum
    — the f32 row never exists in HBM.  Same tile schedule (an int8 tile
    holds 32 rows, so a candidate still moves one 4 KiB tile), same
    ``(B, C, d)``-free guarantee, and the same elementwise reduction that
    makes the XLA twin bit-identical under any chunking."""
    d = x.shape[1]
    qparams = (scale.astype(jnp.float32).reshape(1, d),
               zero.astype(jnp.float32).reshape(1, d))
    return _rows_call(x, idx, q, tiles, qparams, interpret)


@functools.partial(jax.jit, static_argnames=("chunk",))
def expand_score_q_xla(
    x: jnp.ndarray,      # (n, d) int8
    scale: jnp.ndarray,  # (d,) f32
    zero: jnp.ndarray,   # (d,) f32
    idx: jnp.ndarray,    # (B, C) int32, -1 = masked
    q: jnp.ndarray,      # (B, d)
    *,
    chunk: int = 32,
) -> jnp.ndarray:
    """CPU-CI twin of :func:`expand_score_q`: identical dequant + elementwise
    network over ``chunk``-wide candidate slices (peak ``(B, chunk, d)``,
    never ``(B, C, d)``); bit-identical to the Pallas kernel."""
    B, C = idx.shape
    n, d = x.shape
    q32 = q.astype(jnp.float32)
    s32 = scale.astype(jnp.float32)
    z32 = zero.astype(jnp.float32)
    chunk = max(min(chunk, (C + 1) // 2 if C > 1 else 1), 1)
    Cp = ((C + chunk - 1) // chunk) * chunk
    safe = jnp.clip(idx, 0, n - 1).astype(jnp.int32)
    if Cp != C:
        safe = jnp.pad(safe, ((0, 0), (0, Cp - C)))

    def body(t, acc):
        sl = jax.lax.dynamic_slice_in_dim(safe, t * chunk, chunk, axis=1)
        rows = x[sl].astype(jnp.float32)               # (B, chunk, d) int8→f32
        diff = q32[:, None, :] - (rows * s32 + z32)
        dc = jnp.sum(diff * diff, axis=-1)             # (B, chunk)
        return jax.lax.dynamic_update_slice_in_dim(acc, dc, t * chunk, axis=1)

    out = jax.lax.fori_loop(
        0, Cp // chunk, body, jnp.zeros((B, Cp), jnp.float32)
    )[:, :C]
    return jnp.where(idx >= 0, out, jnp.inf)


@jax.jit
def expand_score_q_legacy(
    x: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray,
    idx: jnp.ndarray, q: jnp.ndarray,
) -> jnp.ndarray:
    """Pre-fusion baseline on the quantized plane: materialize the dequantized
    ``(B, C, d)`` gather, score with the matmul identity (A/B profiling)."""
    n = x.shape[0]
    q32 = q.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1)
    safe = jnp.clip(idx, 0, n - 1)
    rows = x[safe].astype(jnp.float32) * scale.astype(jnp.float32) \
        + zero.astype(jnp.float32)                     # (B, C, d) gather
    xn = jnp.sum(rows * rows, axis=-1)
    ip = jnp.einsum("bcd,bd->bc", rows, q32)
    dist = jnp.maximum(xn + qn[:, None] - 2.0 * ip, 0.0)
    return jnp.where(idx >= 0, dist, jnp.inf)


# ---------------------------------------------------------------- pallas (pq)
@jax.jit
def pq_lut(codebooks: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Per-query subspace distance tables: ``lut[b, j, k]`` is the squared
    L2 between query ``b``'s ``j``-th subvector and centroid ``k`` of
    subspace ``j`` — computed **once per batch** (ADC, Jégou et al. 2011).

    Each entry is an independent elementwise square-difference sum over the
    ``d/m`` subspace dims, so per-row tables are bitwise invariant under
    batch composition — the same invariance contract as the fused distance
    kernels (module docstring).  The transient ``(B, m, 256, d/m)`` diff
    is ``256·d·4`` bytes per query; at very large ``B·d`` it can be chunked
    over subspaces without changing a single bit (entries are independent).
    """
    B = q.shape[0]
    m, k, dsub = codebooks.shape
    qs = q.astype(jnp.float32).reshape(B, m, dsub)
    diff = qs[:, :, None, :] - codebooks[None]         # (B, m, K, dsub)
    return jnp.sum(diff * diff, axis=-1)               # (B, m, K)


def _fold_sum_m(vals: jnp.ndarray) -> jnp.ndarray:
    """Strict left-to-right sum over the last (subspace) axis.

    ``m`` is a small static constant, so this unrolls to a chain of adds.
    Both PQ backends reduce through this fold — a bare ``jnp.sum`` lets the
    compiler pick a backend-dependent association order over the ``m``
    lookups, which breaks the bit-identity contract (f32 adds don't
    reassociate)."""
    out = vals[..., 0]
    for j in range(1, vals.shape[-1]):
        out = out + vals[..., j]
    return out


def _kernel_pq(ids_ref, lut_ref, codes_hbm, o_ref, buf, sem, *, C, K, R, m):
    """ADC scoring: each gathered code row indexes query ``b``'s transposed
    ``(256, m)`` tables with a one-hot sublane sum (exact: one entry plus
    zeros), then :func:`_fold_sum_m` adds the ``m`` lookups in the twin's
    order."""
    def consume(b, p, slot):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

        def one(k, acc):
            t = buf[slot, k].astype(jnp.int32)
            s = jnp.maximum(ids_ref[b * C + p * K + k], 0) % R
            code = _select_row(t, s)[:, :m]                    # (1, m)
            lut = lut_ref[b]                                   # (256, m)
            hit = jax.lax.broadcasted_iota(jnp.int32, lut.shape, 0) == code
            vals = jnp.sum(jnp.where(hit, lut, jnp.zeros_like(lut)),
                           axis=0, keepdims=True)              # (1, m)
            dist = vals[:, 0:1]                     # _fold_sum_m's order
            for j in range(1, m):
                dist = dist + vals[:, j:j + 1]
            return jnp.where(lane == k, dist, acc)

        acc = jax.lax.fori_loop(0, K, one, jnp.zeros((1, K), jnp.float32))
        o_ref[b:b + 1, _lanes(p, K)] = acc

    _gather_tiles(ids_ref, codes_hbm, buf, sem, tb=_TB, C=C, K=K, R=R,
                  consume=consume)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expand_score_pq(
    codes: jnp.ndarray,      # (n, m) uint8 PQ codes (stay in HBM)
    codebooks: jnp.ndarray,  # (m, 256, d/m) f32 frozen codebooks
    idx: jnp.ndarray,        # (B, C) int32 candidate ids (-1 = masked/padding)
    q: jnp.ndarray,          # (B, d) queries
    *,
    interpret: bool = False,
    lut: jnp.ndarray | None = None,
    tiles: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """PQ-plane :func:`expand_score`: squared L2 between ``q[b]`` and the
    *decoded* row ``idx[b, c]``, without ever decoding it.  The per-query
    ``(m, 256)`` LUT is built once per batch (:func:`pq_lut`, or passed in
    precomputed by the fused search loop); each candidate then costs one
    code-tile DMA — the same tile schedule as the f32/int8 kernels — and
    ``m`` table lookups in-register.  Neither a ``(B, C, d)`` gather nor a
    decoded ``(n, d)`` corpus ever exists."""
    B, C = idx.shape
    n, m = codes.shape
    if lut is None:
        lut = pq_lut(codebooks, q)
    if tiles is None:
        tiles = tile_view(codes)
    Bp, Cp = _padded_dims(B, C)
    R = tiles.shape[1]
    K = _chunk_width(Cp, R * tiles.shape[2])
    lut_t = jnp.pad(jnp.swapaxes(lut, 1, 2), ((0, Bp - B), (0, 0), (0, 0)))
    kernel = functools.partial(_kernel_pq, C=Cp, K=K, R=R, m=m)
    out = _row_gather_call(
        kernel, _flat_ids(idx, n, Bp, Cp), (lut_t,), tiles,
        Bp=Bp, Cp=Cp, K=K, extra_scratch=[], name="expand_score_pq",
        interpret=interpret,
    )[:B, :C]
    return jnp.where(idx >= 0, out, jnp.inf)


@functools.partial(jax.jit, static_argnames=("chunk",))
def expand_score_pq_xla(
    codes: jnp.ndarray,      # (n, m) uint8
    codebooks: jnp.ndarray,  # (m, 256, d/m) f32
    idx: jnp.ndarray,        # (B, C) int32, -1 = masked
    q: jnp.ndarray,          # (B, d)
    *,
    chunk: int = 32,
    lut: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """CPU-CI twin of :func:`expand_score_pq`: the same once-per-batch LUT
    (:func:`pq_lut`), then a ``fori_loop`` over ``chunk``-wide candidate
    slices gathering ``(B, chunk, m)`` uint8 code rows and summing the
    ``m`` table lookups per row.  Lookups index identical LUT entries and
    the sum runs over subspaces in the same order as the Pallas kernel, so
    the two are bit-identical for any ``chunk`` and batch composition."""
    B, C = idx.shape
    n, m = codes.shape
    if lut is None:
        lut = pq_lut(codebooks, q)                     # (B, m, K)
    chunk = max(min(chunk, (C + 1) // 2 if C > 1 else 1), 1)
    Cp = ((C + chunk - 1) // chunk) * chunk
    safe = jnp.clip(idx, 0, n - 1).astype(jnp.int32)
    if Cp != C:
        safe = jnp.pad(safe, ((0, 0), (0, Cp - C)))

    def body(t, acc):
        sl = jax.lax.dynamic_slice_in_dim(safe, t * chunk, chunk, axis=1)
        rows = codes[sl].astype(jnp.int32)             # (B, chunk, m) code rows
        vals = jnp.take_along_axis(                    # (B, chunk, m) lookups
            lut[:, None, :, :], rows[..., None], axis=-1
        )[..., 0]
        dc = _fold_sum_m(vals)                         # (B, chunk)
        return jax.lax.dynamic_update_slice_in_dim(acc, dc, t * chunk, axis=1)

    out = jax.lax.fori_loop(
        0, Cp // chunk, body, jnp.zeros((B, Cp), jnp.float32)
    )[:, :C]
    return jnp.where(idx >= 0, out, jnp.inf)


@jax.jit
def expand_score_pq_legacy(
    codes: jnp.ndarray, codebooks: jnp.ndarray,
    idx: jnp.ndarray, q: jnp.ndarray,
) -> jnp.ndarray:
    """Pre-fusion baseline on the PQ plane: decode the **entire corpus** to
    ``(n, d)`` f32, then the full ``(B, C, d)`` gather + matmul identity —
    both intermediates the fused pair exists to avoid (A/B profiling)."""
    n, m = codes.shape
    k, dsub = codebooks.shape[1:]
    flat = codebooks.reshape(m * k, dsub)
    offs = (jnp.arange(m, dtype=jnp.int32) * k)[None, :]
    dec = flat[codes.astype(jnp.int32) + offs].reshape(n, m * dsub)
    return expand_score_legacy(dec, idx, q)


# --------------------------------------------------------------------- xla
@functools.partial(jax.jit, static_argnames=("chunk",))
def expand_score_xla(
    x: jnp.ndarray,     # (n, d)
    idx: jnp.ndarray,   # (B, C) int32, -1 = masked
    q: jnp.ndarray,     # (B, d)
    *,
    chunk: int = 32,
) -> jnp.ndarray:
    """CPU-CI twin of :func:`expand_score`: identical elementwise network,
    traced as a ``fori_loop`` over ``chunk``-wide candidate slices so the
    peak intermediate is ``(B, chunk, d)`` — never ``(B, C, d)``.

    Bit-identical to the Pallas kernel for any ``chunk`` (elementwise
    per-row reduction; see module docstring)."""
    B, C = idx.shape
    n, d = x.shape
    q32 = q.astype(jnp.float32)
    # Never a single full-width chunk: chunk == C would materialize exactly
    # the (B, C, d) gather this twin exists to avoid.
    chunk = max(min(chunk, (C + 1) // 2 if C > 1 else 1), 1)
    Cp = ((C + chunk - 1) // chunk) * chunk
    safe = jnp.clip(idx, 0, n - 1).astype(jnp.int32)
    if Cp != C:
        safe = jnp.pad(safe, ((0, 0), (0, Cp - C)))

    def body(t, acc):
        sl = jax.lax.dynamic_slice_in_dim(safe, t * chunk, chunk, axis=1)
        rows = x[sl].astype(jnp.float32)               # (B, chunk, d)
        diff = q32[:, None, :] - rows
        dc = jnp.sum(diff * diff, axis=-1)             # (B, chunk)
        return jax.lax.dynamic_update_slice_in_dim(acc, dc, t * chunk, axis=1)

    out = jax.lax.fori_loop(
        0, Cp // chunk, body, jnp.zeros((B, Cp), jnp.float32)
    )[:, :C]
    return jnp.where(idx >= 0, out, jnp.inf)


# ------------------------------------------------------------------ legacy
@jax.jit
def expand_score_legacy(x: jnp.ndarray, idx: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Pre-fusion baseline: materialize the ``(B, C, d)`` gather, score with
    the matmul identity.  Kept for the A/B memory/QPS profile only."""
    n = x.shape[0]
    q32 = q.astype(jnp.float32)
    qn = jnp.sum(q32 * q32, axis=-1)
    xn = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
    safe = jnp.clip(idx, 0, n - 1)
    rows = x[safe].astype(jnp.float32)                 # (B, C, d) gather
    ip = jnp.einsum("bcd,bd->bc", rows, q32)
    dist = jnp.maximum(xn[safe] + qn[:, None] - 2.0 * ip, 0.0)
    return jnp.where(idx >= 0, dist, jnp.inf)


# ------------------------------------------------------------------- dedup
def dedup_first(ids: jnp.ndarray, flag: jnp.ndarray) -> jnp.ndarray:
    """Per row, keep ``flag`` only on the first (lowest-index) flagged slot
    carrying each id — sort-based, ``O(C log C)``, no ``(·, C, C)`` tensor.

    Unflagged slots neither survive nor suppress later duplicates (they sort
    behind an id sentinel).  The stable argsort breaks equal-id ties by the
    original slot index, so "first of each sorted run" is exactly "lowest
    original index", matching :func:`dedup_first_quadratic` bit-for-bit.
    Integer-only: the id sort never touches the distance floats, which is
    why the search's bit-identity contract survives it (DESIGN.md §10).
    """
    sentinel = jnp.iinfo(jnp.int32).max
    key = jnp.where(flag, ids.astype(jnp.int32), sentinel)
    order = jnp.argsort(key, axis=-1, stable=True)
    sk = jnp.take_along_axis(key, order, axis=-1)
    run_start = jnp.concatenate(
        [jnp.ones(sk.shape[:-1] + (1,), bool), sk[..., 1:] != sk[..., :-1]],
        axis=-1,
    )
    keep_sorted = run_start & (sk != sentinel)
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(keep_sorted, inv, axis=-1)


def dedup_first_quadratic(ids: jnp.ndarray, flag: jnp.ndarray) -> jnp.ndarray:
    """The pre-fusion ``O(C²)`` pairwise-mask dedup (two ``(·, C, C)``
    boolean intermediates per call) — the oracle/baseline ``dedup_first``
    must match bit-for-bit."""
    C = ids.shape[-1]
    same = ids[..., :, None] == ids[..., None, :]          # (..., C, C)
    slot = jnp.arange(C, dtype=jnp.int32)
    earlier = slot[:, None] > slot[None, :]
    return flag & ~jnp.any(same & earlier & flag[..., None, :], axis=-1)
