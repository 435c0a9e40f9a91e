"""Hand-rolled overlap-friendly collectives (ring all-gather / reduce-scatter
via ``ppermute``) for shard_map code paths.

XLA already emits tuned collectives for jit-traced code; these exist for the
places where we *schedule* communication ourselves to overlap with compute —
the ring-streamed KNN build (core/sharded.py) and the §Perf experiments that
compare one-shot vs ring schedules (each ring hop's ppermute can execute
concurrently with the consumer's matmul on the previously received block).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def ring_all_gather(x: jnp.ndarray, axis_name: str):
    """All-gather along ``axis_name`` as n-1 ppermute hops.

    Returns (size, x_full) where x_full has a new leading shard axis in ring
    order starting at the local shard.
    """
    size = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(carry, _):
        blk = carry
        nxt = jax.lax.ppermute(blk, axis_name, perm)
        return nxt, blk

    _, blocks = jax.lax.scan(step, x, None, length=size)
    return size, blocks  # (size, *x.shape), blocks[0] == local shard


def ring_reduce_scatter(x: jnp.ndarray, axis_name: str):
    """Reduce-scatter (sum) of a (size, chunk, ...) array along the ring.

    Each rank ends with the fully-reduced chunk ``x[rank]``.
    """
    size = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(carry, k):
        acc = carry  # running partial for the chunk we'll pass on
        # the partial arriving at hop k belongs to chunk (me - k - 2) mod n:
        # chunk c starts at rank c+1 and completes at rank c after n-1 hops
        idx = (me - k - 2) % size
        acc = jax.lax.ppermute(acc, axis_name, perm) + x[idx]
        return acc, None

    init = x[(me - 1) % size]   # chunk me-1 starts its journey here
    acc, _ = jax.lax.scan(step, init, jnp.arange(size - 1))
    return acc


def ring_streamed_map(
    x_block: jnp.ndarray,
    axis_name: str,
    fold: Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray],
    init,
):
    """Stream every rank's block past every other rank (the KNN-build pattern).

    ``fold(acc, visiting_block, src_rank) -> acc`` runs once per hop while
    the next ppermute is in flight (overlap by construction: the permute's
    result is not needed until the next iteration).
    """
    size = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(carry, k):
        blk, acc = carry
        src = (me - k) % size
        acc = fold(acc, blk, src)
        blk = jax.lax.ppermute(blk, axis_name, perm)
        return (blk, acc), None

    (_, acc), _ = jax.lax.scan(step, (x_block, init), jnp.arange(size))
    return acc
