"""Bitonic partial-merge Pallas TPU kernel for the fused beam search.

The hot loop of Alg. 4 must fold freshly scored neighbor candidates into the
sorted ``ef``-beam every step.  The legacy path re-sorts the whole
``(ef + M)`` concatenation with a full ``argsort`` per single-node expansion;
this kernel replaces that with the classic bitonic *partial* merge
(DESIGN.md §8):

1. bitonic-sort the ``L = W·M`` candidates (``L/2·O(log²L)``
   compare-exchanges, all vectorized over the lane axis);
2. keep the best ``E`` candidates in descending order (the sort runs
   descending, so no lane reversal is needed) and take the elementwise
   minimum against the (already sorted) beam — the first stage of a
   bitonic merge of the length-``2E`` concatenation, which provably yields
   the ``E`` smallest elements of the union as a bitonic sequence;
3. one bitonic merge pass (``log E`` stages) re-sorts that sequence.

Amortized over the ``W`` nodes expanded per step this is several times fewer
comparator ops than the legacy argsort (see :func:`merge_comparator_count`).

Keys are f32 distances; each key carries one packed int32 payload
(``id << 1 | expanded_bit`` in the search; opaque here).  All comparisons use
the total order ``(key, payload)`` so ties are deterministic and the Pallas
and XLA backends produce **bit-identical** outputs: both run the same network
below — ``pallas`` through ``pl.pallas_call`` (Mosaic on TPU, interpret mode
on CPU), ``xla`` as plain traced jnp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import compiler_params, pad_to

PAD_PAYLOAD = -2  # (id=-1) << 1 | 0 — what empty beam/candidate slots carry


def next_pow2(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p


def _cmp_swap(d, p, j: int, up=None):
    """One compare-exchange stage between lanes ``i`` and ``i ^ j``.

    ``up`` is ``None`` (every block ascending) or a pair of complementary
    bool masks ``(asc, desc)`` broadcastable to ``d`` giving the sort
    direction of the block each element belongs to.  Comparison is on the
    total order ``(d, p)``; the network is built from comparisons and
    and/or only (no bool constants or selects of masks, which Mosaic cannot
    lower).
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, d.ndim - 1)
    is_lo = (idx & j) == 0
    is_hi = (idx & j) != 0
    pd = jnp.where(is_lo, jnp.roll(d, -j, axis=-1), jnp.roll(d, j, axis=-1))
    pp = jnp.where(is_lo, jnp.roll(p, -j, axis=-1), jnp.roll(p, j, axis=-1))
    before = (pd < d) | ((pd == d) & (pp < p))   # partner precedes self
    after = (pd > d) | ((pd == d) & (pp > p))    # partner follows self
    take_asc = (is_lo & before) | (is_hi & after)
    if up is None:
        take = take_asc
    else:
        asc, desc = up
        take = (asc & take_asc) | (desc & ((is_lo & after) | (is_hi & before)))
    return jnp.where(take, pd, d), jnp.where(take, pp, p)


def _bitonic_sort(d, p, *, descending: bool = False):
    """Full bitonic sort along the last axis (power-of-two length).

    ``descending`` flips every stage's direction, which mirrors the whole
    network: the output is the ascending result reversed, bit for bit
    (``(d, p)`` is a total order)."""
    L = d.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, d.ndim - 1)
    k = 2
    while k <= L:
        up = ((idx & k) == 0, (idx & k) != 0)
        if descending:
            up = up[::-1]
        j = k // 2
        while j >= 1:
            d, p = _cmp_swap(d, p, j, up)
            j //= 2
        k *= 2
    return d, p


def _merge_block(beam_d, beam_p, cand_d, cand_p):
    """Merge sorted beam (..., E) with unsorted candidates (..., L): return
    the E smallest of the union, ascending in the ``(d, p)`` total order."""
    E = beam_d.shape[-1]
    L = cand_d.shape[-1]
    # Sorting the candidates *descending* leaves their best E at the tail,
    # already in the reversed order the bitonic merge stage pairs against
    # the ascending beam — no lane reversal (which Mosaic cannot lower).
    rd, rp = _bitonic_sort(cand_d, cand_p, descending=True)
    if L >= E:
        rd = rd[..., L - E:]
        rp = rp[..., L - E:]
    else:
        lead = cand_d.shape[:-1] + (E - L,)
        rd = jnp.concatenate([jnp.full(lead, jnp.inf, rd.dtype), rd], axis=-1)
        rp = jnp.concatenate(
            [jnp.full(lead, PAD_PAYLOAD, rp.dtype), rp], axis=-1)
    le = (beam_d < rd) | ((beam_d == rd) & (beam_p <= rp))
    md = jnp.where(le, beam_d, rd)
    mp = jnp.where(le, beam_p, rp)
    j = E // 2
    while j >= 1:
        md, mp = _cmp_swap(md, mp, j)
        j //= 2
    return md, mp


# --------------------------------------------------------------------- xla
@jax.jit
def beam_merge_xla(beam_d, beam_p, cand_d, cand_p):
    """Reference backend: the identical network as plain traced jnp."""
    cand_d, cand_p = _pad_candidates(cand_d, cand_p)
    return _merge_block(beam_d, beam_p, cand_d, cand_p)


# ------------------------------------------------------------------ pallas
def _kernel(bd_ref, bp_ref, cd_ref, cp_ref, od_ref, op_ref):
    nd, np_ = _merge_block(bd_ref[...], bp_ref[...], cd_ref[...], cp_ref[...])
    od_ref[...] = nd
    op_ref[...] = np_


def _pad_candidates(cand_d, cand_p):
    """Pad candidate length to a power of two (pad slots sort last)."""
    L = cand_d.shape[-1]
    Lp = next_pow2(max(L, 2))
    if Lp != L:
        pad = [(0, 0)] * (cand_d.ndim - 1) + [(0, Lp - L)]
        cand_d = jnp.pad(cand_d, pad, constant_values=jnp.inf)
        cand_p = jnp.pad(cand_p, pad, constant_values=PAD_PAYLOAD)
    return cand_d, cand_p


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def beam_merge(
    beam_d: jnp.ndarray,   # (B, E) f32, ascending (E power of two)
    beam_p: jnp.ndarray,   # (B, E) int32 packed payloads
    cand_d: jnp.ndarray,   # (B, L) f32, +inf for invalid slots
    cand_p: jnp.ndarray,   # (B, L) int32
    *,
    bb: int = 8,
    interpret: bool = False,
):
    """Pallas backend: grid over row blocks, whole network in one kernel."""
    B, E = beam_d.shape
    if E & (E - 1):
        raise ValueError(f"beam width must be a power of two, got {E}")
    cand_d, cand_p = _pad_candidates(cand_d, cand_p)
    L = cand_d.shape[1]
    Bp = pad_to(B, bb)
    if Bp != B:
        rpad = ((0, Bp - B), (0, 0))
        beam_d = jnp.pad(beam_d, rpad, constant_values=jnp.inf)
        beam_p = jnp.pad(beam_p, rpad, constant_values=PAD_PAYLOAD)
        cand_d = jnp.pad(cand_d, rpad, constant_values=jnp.inf)
        cand_p = jnp.pad(cand_p, rpad, constant_values=PAD_PAYLOAD)
    out_d, out_p = pl.pallas_call(
        _kernel,
        grid=(Bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, E), lambda i: (i, 0)),
            pl.BlockSpec((bb, E), lambda i: (i, 0)),
            pl.BlockSpec((bb, L), lambda i: (i, 0)),
            pl.BlockSpec((bb, L), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, E), lambda i: (i, 0)),
            pl.BlockSpec((bb, E), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, E), jnp.float32),
            jax.ShapeDtypeStruct((Bp, E), jnp.int32),
        ],
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret,
        name="beam_merge",
    )(beam_d, beam_p, cand_d, cand_p)
    return out_d[:B], out_p[:B]


# -------------------------------------------------------------- cost model
def merge_comparator_count(ef: int, M: int, *, width: int = 1, fused: bool = True) -> float:
    """Comparator ops per *expansion* for the beam-maintenance step.

    Legacy path: one full ``argsort`` of the ``(ef + M)`` concatenation per
    single-node expansion — modeled as a bitonic sort of the padded length.
    Fused path: sort ``L = next_pow2(width·M)`` candidates + one partial
    merge into the ``E = next_pow2(ef)`` beam, amortized over ``width``
    expansions.
    """
    import math

    def bitonic_sort_cost(n: int) -> float:
        lg = max(int(math.ceil(math.log2(n))), 1)
        return n / 2 * lg * (lg + 1) / 2

    if not fused:
        return bitonic_sort_cost(next_pow2(ef + M))
    E = next_pow2(ef)
    L = next_pow2(max(width * M, 2))
    merge = E + (E / 2) * max(int(math.log2(E)), 1)
    return (bitonic_sort_cost(L) + merge) / width
