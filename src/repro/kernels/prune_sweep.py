"""Fused unified-prune sweep kernel (paper Alg. 3, tile-by-tile).

Construction cost is dominated by the pruning sweep: for every node ``u`` the
candidates are scanned in ascending-distance order and candidate ``t``
survives unless an already-retained ``w < t`` witnesses it — geometrically
(``α²·δ²(t,w) < δ²(u,t)``) *and* semantically (``Φ_IF`` / ``Φ_IS``,
Def. 3.1).  The legacy implementation materializes, per node block, the full
``(B, C, C)`` pairwise-distance tensor **plus two ``(B, C, C)`` boolean Φ
witness tensors** in HBM before the scan even starts — at build shapes
(``B = 1024``, ``C ≈ 400``) that is hundreds of MB per block and the
dominant HBM traffic of the build (DESIGN.md §9).

The fused sweep never forms any ``(·, C, C)`` tensor.  Each scan step
recomputes, on the fly and only for the current candidate ``t``:

* the distance **row** ``δ²(t, ·)`` — a ``(B, C)`` tile of VPU work;
* the Φ witness **rows** ``Φ_IF(u, t, ·)`` / ``Φ_IS(u, t, ·)`` — six
  comparisons against the hull / intersection of ``(I_u, I_t)``.

Peak live memory per step drops from ``O(B·C²)`` to ``O(B·C)``; the arrays
that stay resident are exactly the kernel inputs (``O(B·C·d)``).

Backends run the *identical* network: ``pallas`` through ``pl.pallas_call``
(Mosaic on TPU, interpret mode on CPU) with the batch tiled ``bb`` rows per
grid cell, ``xla`` as the same block function traced over the full batch,
and ``legacy`` as the materialize-everything-then-scan baseline.  All three
produce **bit-identical** ``status`` / repair outputs:

* every float entering a comparison is produced by :func:`cand_row_dist`,
  an *elementwise* square-difference sum.  Unlike the matmul identity the
  legacy path used to rely on (whose Eigen/MXU reduction order — and hence
  low bits — changes with the batch shape), the elementwise form is
  bitwise invariant under row blocking, so any ``bb`` tiling agrees with
  the untiled trace;
* everything else in the scan is boolean/integer algebra (exact).

The shared preprocessing (dedup, distance sort, gathers) lives in
``core/prune.py``; this module only consumes its fixed-shape outputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import intervals as iv
from repro.kernels.util import compiler_params, pad_to


def cand_row_dist(xs: jnp.ndarray, t, x_t: jnp.ndarray | None = None) -> jnp.ndarray:
    """Distance row ``δ²(c_t, c_w)`` for all ``w``: (B, C, d) → (B, C).

    Elementwise square-difference sum (VPU), *not* the matmul identity: the
    per-element reduction over ``d`` is bitwise independent of the batch
    blocking, which the cross-backend bit-identity contract requires.
    ``x_t`` is candidate ``t``'s ``(B, d)`` vector when the caller already
    read it (the Pallas kernel slices it from its ref).
    """
    if x_t is None:
        x_t = jax.lax.dynamic_index_in_dim(xs, t, axis=1, keepdims=False)
    diff = xs - x_t[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def _col(a: jnp.ndarray, hit: jnp.ndarray) -> jnp.ndarray:
    """Column ``a[:, t]`` given ``hit = (col_idx == t)``: a masked lane sum,
    exact (one value plus zeros) and free of dynamic slices, which Mosaic
    cannot lower on loaded values."""
    return jnp.sum(jnp.where(hit, a, jnp.zeros_like(a)), axis=1)


def sweep_block(
    i_u: jnp.ndarray,      # (B, 2)  node intervals
    xs: jnp.ndarray,       # (B, C, d) candidate vectors (distance-sorted)
    ic_l: jnp.ndarray,     # (B, C) candidate interval left ends
    ic_r: jnp.ndarray,     # (B, C) candidate interval right ends
    d_uc: jnp.ndarray,     # (B, C) sorted δ²(u, ·), +inf pads
    valid: jnp.ndarray,    # (B, C) live candidate mask
    overlap: jnp.ndarray,  # (B, C) I_u ∩ I_c ≠ ∅ (all-True when not unified)
    *,
    m_if: int,
    m_is: int,
    alpha: float,
    unified: bool,
    row_at=None,
):
    """The fused Alg. 3 scan over one row block; Φ rows computed per step.

    Returns ``(status int32 (B, C), rep_if, rep_is)`` with repair slots
    *local* to the candidate axis (-1 = kept / invalid).  Per-step columns
    are read with masked lane sums and written with selects, and the
    retained sets are carried as int32 0/1 — one network that both the XLA
    twin and the Mosaic kernel lower.  ``row_at(t)`` returns candidate
    ``t``'s ``(B, d)`` vector (default: a dynamic slice of ``xs``).
    """
    B, C = d_uc.shape
    alpha2 = jnp.float32(alpha) ** 2
    col_idx = jax.lax.broadcasted_iota(jnp.int32, (B, C), 1)
    valid = valid.astype(jnp.int32)
    overlap = overlap.astype(jnp.int32)

    def body(t, state):
        act_if, act_is, cnt_if, cnt_is, rep_if, rep_is = state
        hit = col_idx == t
        x_t = None if row_at is None else row_at(t)
        d_row = cand_row_dist(xs, t, x_t)                      # (B, C)
        if unified:
            it_l = _col(ic_l, hit)                             # (B,)
            it_r = _col(ic_r, hit)
            hull_l = jnp.minimum(i_u[:, 0], it_l)
            hull_r = jnp.maximum(i_u[:, 1], it_r)
            phi_if_row = (hull_l[:, None] <= ic_l) & (ic_r <= hull_r[:, None])
            int_l = jnp.maximum(i_u[:, 0], it_l)
            int_r = jnp.minimum(i_u[:, 1], it_r)
            nonempty = int_l <= int_r
            phi_is_row = (
                nonempty[:, None]
                & (ic_l <= int_l[:, None])
                & (ic_r >= int_r[:, None])
            )
        else:
            phi_if_row = col_idx >= 0
            phi_is_row = col_idx >= 0

        s_if = _col(valid, hit) > 0
        s_is = s_if & (_col(overlap, hit) > 0)

        # Witness scan (Alg. 3 lines 9-17), vectorized over the retained prefix.
        geo = (col_idx < t) & (alpha2 * d_row < _col(d_uc, hit)[:, None])
        wit_if = geo & (act_if > 0) & phi_if_row
        wit_is = geo & (act_is > 0) & phi_is_row
        # first witness (C when there is none — only read when pruned)
        j_if = jnp.min(jnp.where(wit_if, col_idx, C), axis=1)
        j_is = jnp.min(jnp.where(wit_is, col_idx, C), axis=1)
        pruned_if = j_if < C
        pruned_is = j_is < C

        keep_if = s_if & ~pruned_if & (cnt_if < m_if)
        keep_is = s_is & ~pruned_is & (cnt_is < m_is)
        cnt_if = cnt_if + keep_if.astype(jnp.int32)
        cnt_is = cnt_is + keep_is.astype(jnp.int32)

        act_if = jnp.where(hit, keep_if.astype(jnp.int32)[:, None], act_if)
        act_is = jnp.where(hit, keep_is.astype(jnp.int32)[:, None], act_is)
        rep_if = jnp.where(hit, jnp.where(s_if & pruned_if, j_if, -1)[:, None], rep_if)
        rep_is = jnp.where(hit, jnp.where(s_is & pruned_is, j_is, -1)[:, None], rep_is)
        return act_if, act_is, cnt_if, cnt_is, rep_if, rep_is

    init = (
        jnp.zeros((B, C), jnp.int32),
        jnp.zeros((B, C), jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.full((B, C), -1, jnp.int32),
        jnp.full((B, C), -1, jnp.int32),
    )
    act_if, act_is, _, _, rep_if, rep_is = jax.lax.fori_loop(0, C, body, init)
    status = act_if * iv.FLAG_IF + act_is * iv.FLAG_IS
    return status, rep_if, rep_is


# ----------------------------------------------------------------------- xla
@functools.partial(jax.jit, static_argnames=("m_if", "m_is", "alpha", "unified"))
def prune_sweep_xla(i_u, xs, i_c, d_uc, valid, overlap, *, m_if, m_is, alpha, unified):
    """Reference fused backend: the identical network as plain traced jnp."""
    return sweep_block(
        i_u, xs, i_c[..., 0], i_c[..., 1], d_uc, valid, overlap,
        m_if=m_if, m_is=m_is, alpha=alpha, unified=unified,
    )


# -------------------------------------------------------------------- pallas
_XS_BLOCK_BYTES = 4 << 20   # one (bb, C, d) candidate block in VMEM
_VMEM_LIMIT = 48 << 20


def sweep_rows(C: int, d: int) -> int:
    """Rows per grid cell: the largest of 32, 16, 8 whose ``(bb, C, d)``
    candidate block (lanes padded to 128) fits ``_XS_BLOCK_BYTES``; 8 when
    none does.  The block is double-buffered and each scan step makes
    block-sized temporaries, so a wide pool (the delete repair's 4·M) or a
    wide row takes fewer rows per cell."""
    lanes = -(-d // 128) * 128
    for bb in (32, 16):
        if bb * C * lanes * 4 <= _XS_BLOCK_BYTES:
            return bb
    return 8


@functools.partial(
    jax.jit, static_argnames=("m_if", "m_is", "alpha", "unified", "bb", "interpret")
)
def prune_sweep(
    i_u, xs, i_c, d_uc, valid, overlap,
    *,
    m_if: int,
    m_is: int,
    alpha: float,
    unified: bool,
    bb: int | None = None,
    interpret: bool = False,
):
    """Pallas backend: grid over ``bb``-row tiles (default
    :func:`sweep_rows`), whole sweep in one kernel."""
    B, C = d_uc.shape
    d = xs.shape[-1]
    if bb is None:
        bb = sweep_rows(C, d)
    Bp = pad_to(B, bb)
    # The candidate intervals travel as two (B, C) arrays: a (bb, C, 2)
    # block would pad its last axis to 128 lanes in VMEM.
    ic_l, ic_r = i_c[..., 0], i_c[..., 1]
    # Mask operands cross the pallas_call boundary as int32 (Mosaic cannot
    # take i1 memrefs; every kernel in this repo sticks to f32/i32 operands);
    # the sweep reads them as int32 0/1 — value-exact.
    valid = valid.astype(jnp.int32)
    overlap = overlap.astype(jnp.int32)
    if Bp != B:
        r = Bp - B
        i_u = jnp.pad(i_u, ((0, r), (0, 0)))
        xs = jnp.pad(xs, ((0, r), (0, 0), (0, 0)))
        ic_l, ic_r, valid, overlap = (
            jnp.pad(a, ((0, r), (0, 0))) for a in (ic_l, ic_r, valid, overlap))
        d_uc = jnp.pad(d_uc, ((0, r), (0, 0)), constant_values=jnp.inf)

    kernel = functools.partial(
        _kernel, m_if=m_if, m_is=m_is, alpha=alpha, unified=unified
    )
    row2 = pl.BlockSpec((bb, C), lambda i: (i, 0))
    status, rep_if, rep_is = pl.pallas_call(
        kernel,
        grid=(Bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, 2), lambda i: (i, 0)),
            pl.BlockSpec((bb, C, d), lambda i: (i, 0, 0)),
        ] + [row2] * 5,
        out_specs=[row2] * 3,
        out_shape=[jax.ShapeDtypeStruct((Bp, C), jnp.int32)] * 3,
        compiler_params=compiler_params(("arbitrary",),
                                        vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="prune_sweep",
    )(i_u, xs, ic_l, ic_r, d_uc, valid, overlap)
    return status[:B], rep_if[:B], rep_is[:B]


def _kernel(iu_ref, xs_ref, icl_ref, icr_ref, duc_ref, valid_ref, ov_ref,
            st_ref, rif_ref, ris_ref, *, m_if, m_is, alpha, unified):
    status, rep_if, rep_is = sweep_block(
        iu_ref[...], xs_ref[...], icl_ref[...], icr_ref[...], duc_ref[...],
        valid_ref[...], ov_ref[...],
        m_if=m_if, m_is=m_is, alpha=alpha, unified=unified,
        row_at=lambda t: xs_ref[:, pl.ds(t, 1), :][:, 0, :],
    )
    st_ref[...] = status
    rif_ref[...] = rep_if
    ris_ref[...] = rep_is


# -------------------------------------------------------------------- legacy
def _materialize_d_cc(xs: jnp.ndarray) -> jnp.ndarray:
    """Full (B, C, C) pairwise tensor, row by row from :func:`cand_row_dist`
    so the values match the fused backends bit-for-bit."""
    B, C, _ = xs.shape

    def body(t, acc):
        return jax.lax.dynamic_update_slice_in_dim(
            acc, cand_row_dist(xs, t)[:, None, :], t, axis=1
        )

    return jax.lax.fori_loop(0, C, body, jnp.zeros((B, C, C), jnp.float32))


@functools.partial(jax.jit, static_argnames=("m_if", "m_is", "alpha", "unified"))
def prune_sweep_legacy(i_u, xs, i_c, d_uc, valid, overlap, *, m_if, m_is, alpha, unified):
    """Materialize-then-scan baseline (the pre-fusion implementation shape).

    Builds the full ``(B, C, C)`` distance tensor *and* both ``(B, C, C)``
    boolean Φ witness tensors in memory before a per-node scan consumes one
    row per step — the HBM-bound pattern ``bench_build`` quantifies.
    """
    B, C = d_uc.shape
    d_cc = _materialize_d_cc(xs)
    if unified:
        iu_b = jnp.broadcast_to(i_u[:, None, None, :], (B, C, C, 2))
        iv_b = jnp.broadcast_to(i_c[:, :, None, :], (B, C, C, 2))
        iw_b = jnp.broadcast_to(i_c[:, None, :, :], (B, C, C, 2))
        phi_if_mat = iv.phi_if(iu_b, iv_b, iw_b)
        phi_is_mat = iv.phi_is(iu_b, iv_b, iw_b)
    else:
        phi_if_mat = jnp.ones((B, C, C), bool)
        phi_is_mat = jnp.ones((B, C, C), bool)

    alpha2 = jnp.float32(alpha) ** 2
    jrange = jnp.arange(C)

    def one_node(d_cc_n, d_uc_n, valid_n, overlap_n, phi_if_n, phi_is_n):
        def body(t, state):
            act_if, act_is, cnt_if, cnt_is, rep_if, rep_is = state
            v_ok = valid_n[t]
            s_if = v_ok
            s_is = v_ok & overlap_n[t]
            geo = (jrange < t) & (alpha2 * d_cc_n[t] < d_uc_n[t])
            wit_if = geo & act_if & phi_if_n[t]
            wit_is = geo & act_is & phi_is_n[t]
            pruned_if = jnp.any(wit_if)
            pruned_is = jnp.any(wit_is)
            j_if = jnp.argmax(wit_if).astype(jnp.int32)
            j_is = jnp.argmax(wit_is).astype(jnp.int32)
            keep_if = s_if & ~pruned_if & (cnt_if < m_if)
            keep_is = s_is & ~pruned_is & (cnt_is < m_is)
            cnt_if = cnt_if + keep_if.astype(jnp.int32)
            cnt_is = cnt_is + keep_is.astype(jnp.int32)
            act_if = act_if.at[t].set(keep_if)
            act_is = act_is.at[t].set(keep_is)
            rep_if = rep_if.at[t].set(jnp.where(s_if & pruned_if, j_if, -1))
            rep_is = rep_is.at[t].set(jnp.where(s_is & pruned_is, j_is, -1))
            return act_if, act_is, cnt_if, cnt_is, rep_if, rep_is

        init = (
            jnp.zeros((C,), bool),
            jnp.zeros((C,), bool),
            jnp.int32(0),
            jnp.int32(0),
            jnp.full((C,), -1, jnp.int32),
            jnp.full((C,), -1, jnp.int32),
        )
        act_if, act_is, _, _, rep_if, rep_is = jax.lax.fori_loop(0, C, body, init)
        status = act_if.astype(jnp.int32) * iv.FLAG_IF + act_is.astype(jnp.int32) * iv.FLAG_IS
        return status, rep_if, rep_is

    return jax.vmap(one_node)(d_cc, d_uc, valid, overlap, phi_if_mat, phi_is_mat)


# ------------------------------------------------------------ memory profile
def _iter_eqn_avals(jaxpr):
    """Yield output avals of every equation, recursing into sub-jaxprs
    (scan/cond/pallas bodies)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                yield aval
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                yield from _iter_eqn_avals(sub)


def _sub_jaxprs(p):
    from jax.extend import core as jcore

    items = p if isinstance(p, (list, tuple)) else [p]
    for it in items:
        if isinstance(it, jcore.ClosedJaxpr):
            yield it.jaxpr
        elif isinstance(it, jcore.Jaxpr):
            yield it


def sweep_memory_profile(backend: str, B: int = 64, C: int = 96, d: int = 24,
                         *, m_if: int = 32, m_is: int = 32,
                         alpha: float = 1.0, unified: bool = True) -> dict:
    """Trace one sweep and report its intermediate-tensor profile.

    Returns ``{"peak_bytes": max single intermediate, "quadratic": whether
    any (·, C, C)-shaped tensor is materialized}`` — the acceptance check
    that the fused backends never form a Φ (or distance) matrix.
    """
    f32 = jnp.float32
    args = (
        jax.ShapeDtypeStruct((B, 2), f32),
        jax.ShapeDtypeStruct((B, C, d), f32),
        jax.ShapeDtypeStruct((B, C, 2), f32),
        jax.ShapeDtypeStruct((B, C), f32),
        jax.ShapeDtypeStruct((B, C), jnp.bool_),
        jax.ShapeDtypeStruct((B, C), jnp.bool_),
    )
    kw = dict(m_if=m_if, m_is=m_is, alpha=alpha, unified=unified)
    fn = {
        "legacy": functools.partial(prune_sweep_legacy, **kw),
        "xla": functools.partial(prune_sweep_xla, **kw),
        "pallas": functools.partial(prune_sweep, interpret=True, **kw),
    }[backend]
    closed = jax.make_jaxpr(fn)(*args)
    peak = 0
    quadratic = False
    for aval in _iter_eqn_avals(closed.jaxpr):
        size = int(aval.size) * aval.dtype.itemsize if aval.shape else aval.dtype.itemsize
        peak = max(peak, size)
        if len(aval.shape) >= 2 and aval.shape[-1] == C and aval.shape[-2] == C:
            quadratic = True
    return {"peak_bytes": peak, "quadratic": quadratic}
