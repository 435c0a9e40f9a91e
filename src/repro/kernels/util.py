"""Shared Pallas kernel utilities (padding, compiler params, backend probe)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def pad_to(n: int, m: int) -> int:
    """Round ``n`` up to a multiple of ``m`` (at least ``m``)."""
    return max(((n + m - 1) // m) * m, m)


def pad_rows(a: jnp.ndarray, n_pad: int, fill) -> jnp.ndarray:
    """Pad the leading axis of ``a`` to ``n_pad`` rows with ``fill``."""
    n = a.shape[0]
    if n_pad == n:
        return a
    return jnp.concatenate(
        [a, jnp.full((n_pad - n,) + a.shape[1:], fill, a.dtype)], axis=0
    )


def sort_key_i32(x: jnp.ndarray) -> jnp.ndarray:
    """int32 keys that sort exactly as the f32 ``x`` does under
    ``jnp.argsort`` (−0 equals +0; ``x`` holds no NaN): the IEEE bits, with
    the magnitude bits of negatives flipped.  A TPU compiles an int32 sort
    of a million keys faster than an f32 one, whose comparator
    canonicalizes both operands on every compare (PERF.md has the times)."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x).astype(jnp.float32)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


@functools.partial(jax.jit, static_argnames=("n", "width"))
def segment_scatter(
    seg_ids: jnp.ndarray, values: jnp.ndarray, n: int, width: int
) -> jnp.ndarray:
    """Fixed-width per-segment buffers from flat ``(segment, value)`` pairs.

    The one sort-by-segment + rank scatter every fixed-shape "inverted list"
    in this repo reduces to: Alg. 2 repair sets (``build.scatter_repairs``),
    NN-descent reverse edges (``candidates._reverse_candidates``), and the
    in-neighbor sets of the delete-repair sweep (``core/updates.py``).

    Pairs with either side negative are dropped; segment ``s`` keeps the
    first ``width`` surviving values *in scan (flat-index) order* — the
    stable segment sort breaks ties by position, so ``searchsorted`` rank
    equals scan rank.  Returns ``(n, width)`` int32, ``-1``-padded.
    """
    valid = (seg_ids >= 0) & (values >= 0)
    seg = jnp.where(valid, seg_ids, n)
    order = jnp.argsort(seg, stable=True)
    seg_s = seg[order]
    val_s = values[order]
    first = jnp.searchsorted(seg_s, seg_s, side="left")
    rank = jnp.arange(seg_s.shape[0]) - first
    ok = (seg_s < n) & (rank < width)
    out = jnp.full((n + 1, width), -1, jnp.int32)
    out = out.at[jnp.where(ok, seg_s, n), jnp.where(ok, rank, 0)].set(
        jnp.where(ok, val_s, -1), mode="drop"
    )
    return out[:n]


def compiler_params(dimension_semantics: tuple[str, ...], **kw):
    """TPU Mosaic compiler params (``pltpu.CompilerParams``)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=dimension_semantics, **kw)


def on_cpu() -> bool:
    """True when running on the CPU backend → kernels use interpret mode.

    TPU is the *target*; interpret mode executes the kernel body in Python
    for correctness validation (per-kernel tests sweep shapes/dtypes)."""
    return jax.default_backend() == "cpu"
