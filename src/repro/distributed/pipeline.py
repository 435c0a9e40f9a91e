"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

Completes the parallelism matrix (DP/TP/**PP**/EP/SP): layers are split into
``n_stages`` contiguous stages laid out along a mesh axis; microbatches flow
stage-to-stage via ``ppermute`` inside a ``shard_map``.  The schedule is the
classic GPipe loop of ``n_micro + n_stages - 1`` ticks — every stage computes
its resident microbatch then passes activations one hop right, so bubble
fraction = (S-1)/(M+S-1) and the collective per tick is exactly one
boundary activation per stage pair (point-to-point, no all-reduce).

This implementation targets *inference/forward* pipelining (the paper's
serving stack: embedding towers are deep, the index is downstream); for
training, stack it under ``jax.grad`` — ppermute is differentiable, and the
backward pass runs the reverse schedule automatically.

Stage-local layer weights are expected stacked as ``(n_stages, layers_per
_stage, ...)`` pytrees sharded ``P("stage", ...)`` on the pipeline axis.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_forward(
    mesh: Mesh,
    axis: str,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,        # pytree, leaves (n_stages, ...) — sharded on axis
    x_micro: jnp.ndarray,     # (n_micro, mb, ...) microbatched input
):
    """Run ``stage_fn(params_stage, x) -> x`` through all stages.

    Returns (n_micro, mb, ...) outputs (as produced by the LAST stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(x_l, p_l):
        # x_l: (n_micro, mb, ...) replicated; p_l: (1, L/S, ...) this stage's slice
        p_stage = jax.tree.map(lambda a: a[0], p_l)
        sid = jax.lax.axis_index(axis)

        mb_shape = x_l.shape[1:]
        buf = jnp.zeros(mb_shape, x_l.dtype)      # activation resident here
        outs = jnp.zeros_like(x_l)                 # completed microbatches

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when available)
            feed = x_l[jnp.clip(t, 0, n_micro - 1)]
            buf = jnp.where((sid == 0) & (t < n_micro), feed, buf)
            # compute if this stage holds a live microbatch: stage s works on
            # microbatch (t - s) when 0 <= t - s < n_micro
            live = (t - sid >= 0) & (t - sid < n_micro)
            y = stage_fn(p_stage, buf)
            buf = jnp.where(live, y, buf)
            # the last stage retires microbatch (t - n_stages + 1)
            done_idx = t - n_stages + 1
            outs = jax.lax.cond(
                (sid == n_stages - 1) & (done_idx >= 0),
                lambda o: o.at[jnp.clip(done_idx, 0, n_micro - 1)].set(buf),
                lambda o: o,
                outs,
            )
            # shift activations one stage right
            buf = jax.lax.ppermute(buf, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(x_micro, stage_params)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe bubble overhead: (S-1) / (M + S - 1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
