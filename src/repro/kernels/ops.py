"""Jitted public wrappers around the Pallas kernels.

Backend dispatch: on CPU (this container) kernels run in ``interpret=True``
mode — the body executes in Python with identical semantics; on TPU they
compile through Mosaic.  Callers never pass ``interpret`` themselves.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import beam_merge as beam_merge_mod
from repro.kernels import expand_score as expand_score_mod
from repro.kernels import fused_scan, l2dist
from repro.kernels import prune_sweep as prune_sweep_mod
from repro.kernels.util import on_cpu


def resolve_backend(
    backend: str | None, *, choices: tuple[str, ...] = ("pallas", "xla")
) -> str:
    """Default kernel backend: Pallas on TPU, plain-jnp XLA on CPU CI."""
    if backend is None:
        return "xla" if on_cpu() else "pallas"
    if backend not in choices:
        raise ValueError(f"unknown kernel backend {backend!r} (choices {choices})")
    return backend


def pairwise_sq_dist(q: jnp.ndarray, x: jnp.ndarray, **kw) -> jnp.ndarray:
    """Blocked (nq, nx) squared-L2 distance matrix."""
    return l2dist.pairwise_sq_dist(q, x, interpret=on_cpu(), **kw)


def filtered_topk(
    q: jnp.ndarray,
    x: jnp.ndarray,
    obj_int: jnp.ndarray,
    q_int: jnp.ndarray,
    *,
    is_filter: bool,
    k: int,
    **kw,
):
    """Fused predicate + distance + exact top-k in one corpus pass."""
    return fused_scan.filtered_topk(
        q, x, obj_int, q_int, is_filter=is_filter, k=k, interpret=on_cpu(), **kw
    )


def expand_score(
    x: jnp.ndarray, idx: jnp.ndarray, q: jnp.ndarray, *,
    backend: str | None = None, tiles: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Beam-expansion scoring: squared L2 between ``q[b]`` and ``x[idx[b,c]]``
    (``+inf`` where ``idx < 0``).

    ``pallas`` DMAs the HBM tile of each live candidate row, driven by the
    ids (gather in the kernel, never materialized; ``tiles`` is the plane's
    :func:`plane_tiles` view when the caller hoisted it); ``xla`` is the
    bit-identical chunked elementwise twin; ``legacy`` the pre-fusion
    ``(B, C, d)`` gather + matmul baseline kept for A/B profiling.
    """
    resolved = resolve_backend(backend, choices=("pallas", "xla", "legacy"))
    if resolved == "legacy":
        return expand_score_mod.expand_score_legacy(x, idx, q)
    if resolved == "xla":
        return expand_score_mod.expand_score_xla(x, idx, q)
    return expand_score_mod.expand_score(
        x, idx, q, interpret=on_cpu(), tiles=tiles)


def plane_tiles(data: jnp.ndarray, backend: str | None = None) -> jnp.ndarray | None:
    """The HBM tile view the Pallas kernels gather ``data`` (a plane's rows)
    from; None for the other backends.  Padding rows whose width is not a
    multiple of 128 lanes copies them, so callers that score the same plane
    repeatedly (the fused search loop, the delete repair's ``lax.map``)
    build this once and pass it to every :func:`expand_score_plane` /
    :func:`expand_score` call, like the pq LUT."""
    if resolve_backend(backend, choices=("pallas", "xla", "legacy")) != "pallas":
        return None
    return expand_score_mod.tile_view(data)


def pq_lut(plane, q: jnp.ndarray) -> jnp.ndarray | None:
    """Per-query ``(m, 256)`` PQ distance tables for ``plane`` (None for
    non-pq planes).  The fused search loop calls this once per batch and
    hands the result to every :func:`expand_score_plane` step, so the LUT
    build is structurally loop-invariant — not merely hoisted by XLA."""
    if getattr(plane, "tag", None) != "pq":
        return None
    return expand_score_mod.pq_lut(plane.codebooks, q)


def expand_score_plane(
    plane, idx: jnp.ndarray, q: jnp.ndarray, *,
    backend: str | None = None, lut: jnp.ndarray | None = None,
    tiles: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Beam-expansion scoring against a vector *plane* (core/store.py),
    dispatched on the plane's dtype tag.

    ``f32``/``bf16`` route through :func:`expand_score` unchanged (the row
    DMA casts in-register, so bf16 needs no twin); ``int8`` routes through
    the quantized kernels, which dequantize the gathered rows in-register
    (``x·scale + zero``) — same tile schedule, same traced memory profile,
    4× fewer plane bytes in HBM.  ``pq`` routes through the LUT-based kernels: a
    per-query ``(m, 256)`` table built once per batch (pass ``lut`` from
    :func:`pq_lut` to share it across fused-loop steps), then one uint8
    code row gathered per candidate.  ``tiles`` is :func:`plane_tiles`
    hoisted out of the loop the same way.  ``plane`` is
    duck-typed (``tag``/``data``/``scale``/``zero``/``codebooks``) so the
    kernels layer never imports core."""
    if plane.tag == "pq":
        resolved = resolve_backend(backend, choices=("pallas", "xla", "legacy"))
        if resolved == "legacy":
            return expand_score_mod.expand_score_pq_legacy(
                plane.data, plane.codebooks, idx, q)
        if lut is None:
            # One LUT for either backend: the lookups are exact, so the two
            # agree bitwise exactly when their tables do.
            lut = expand_score_mod.pq_lut(plane.codebooks, q)
        if resolved == "xla":
            return expand_score_mod.expand_score_pq_xla(
                plane.data, plane.codebooks, idx, q, lut=lut)
        return expand_score_mod.expand_score_pq(
            plane.data, plane.codebooks, idx, q, interpret=on_cpu(), lut=lut,
            tiles=tiles)
    if plane.tag != "int8":
        return expand_score(plane.data, idx, q, backend=backend, tiles=tiles)
    resolved = resolve_backend(backend, choices=("pallas", "xla", "legacy"))
    if resolved == "legacy":
        return expand_score_mod.expand_score_q_legacy(
            plane.data, plane.scale, plane.zero, idx, q)
    if resolved == "xla":
        return expand_score_mod.expand_score_q_xla(
            plane.data, plane.scale, plane.zero, idx, q)
    return expand_score_mod.expand_score_q(
        plane.data, plane.scale, plane.zero, idx, q, interpret=on_cpu(),
        tiles=tiles)


def gather_sq_dist(x: jnp.ndarray, idx: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Beam-expansion scoring via the Pallas row gather (historical name
    from the absorbed ``kernels/gather_dist.py``)."""
    return expand_score_mod.gather_sq_dist(x, idx, q, interpret=on_cpu())


def prune_sweep(
    i_u, xs, i_c, d_uc, valid, overlap,
    *,
    m_if: int,
    m_is: int,
    alpha: float = 1.0,
    unified: bool = True,
    backend: str | None = None,
    bb: int | None = None,
):
    """Unified interval-aware pruning sweep (Alg. 3) over a node block.

    Returns ``(status int32, rep_if, rep_is)`` with repair slots local to
    the candidate axis.  All three backends run bit-identical scans:
    ``pallas`` tiles the batch ``bb`` rows per grid cell, ``xla`` traces the
    same block function over the whole batch, ``legacy`` materializes the
    ``(B, C, C)`` distance + Φ witness tensors before scanning (the
    pre-fusion baseline kept for A/B benchmarking).
    """
    resolved = resolve_backend(backend, choices=("pallas", "xla", "legacy"))
    kw = dict(m_if=m_if, m_is=m_is, alpha=alpha, unified=unified)
    if resolved == "legacy":
        return prune_sweep_mod.prune_sweep_legacy(
            i_u, xs, i_c, d_uc, valid, overlap, **kw
        )
    if resolved == "xla":
        return prune_sweep_mod.prune_sweep_xla(
            i_u, xs, i_c, d_uc, valid, overlap, **kw
        )
    return prune_sweep_mod.prune_sweep(
        i_u, xs, i_c, d_uc, valid, overlap, bb=bb, interpret=on_cpu(), **kw
    )


def beam_merge(beam_d, beam_p, cand_d, cand_p, *, backend: str | None = None):
    """Bitonic partial merge of scored candidates into the sorted ef-beam.

    Both backends run the identical compare-exchange network (bit-identical
    outputs): ``pallas`` through ``pallas_call`` (interpret on CPU),
    ``xla`` as plain traced jnp.
    """
    if resolve_backend(backend) == "xla":
        return beam_merge_mod.beam_merge_xla(beam_d, beam_p, cand_d, cand_p)
    return beam_merge_mod.beam_merge(
        beam_d, beam_p, cand_d, cand_p, interpret=on_cpu()
    )
