"""Seeded corpus and query stream for one cell, made on the device.

A copy of the Gaussian-mixture generator the repository's paper benchmarks
use (uniform validity intervals, as in the paper's interval model), kept
here so that no later change to the program can move the yardstick.  Every
array of a run comes from one jitted call on ``--seed``: the indexed rows
and the held-out query rows of the same mixture with their windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SEMANTICS = ("IF", "IS", "RF", "RS")
FILTER = {"IF": True, "RF": True, "IS": False, "RS": False}
# The served API's semantics flags: IF/RF filter by containment in the
# window, IS/RS by containing it.
FLAG_IF, FLAG_IS = 1, 2


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: ``jax.random.key`` keeps only 32 bits,
    so the high part is folded in rather than dropped."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=(
    "n", "n_query", "dim", "clusters", "std", "half_min", "half_max"))
def _generate(key, *, n, n_query, dim, clusters, std, half_min,
              half_max):
    kc, ka, ki, kiv, kw, kh = jax.random.split(key, 6)
    total = n + n_query
    centers = jax.random.normal(kc, (clusters, dim))
    assign = jax.random.randint(ka, (total,), 0, clusters)
    x = centers[assign] + jax.random.normal(ki, (total, dim)) * std
    ivs = jnp.sort(jax.random.uniform(kiv, (total, 2)), axis=1)
    c = jax.random.uniform(kw, (n_query,))
    half = jax.random.uniform(kh, (n_query,), minval=half_min, maxval=half_max)
    win = jnp.stack([jnp.maximum(c - half, 0.0), jnp.minimum(c + half, 1.0)], 1)
    point = jnp.stack([c, c], 1)
    return (x.astype(jnp.float32), ivs.astype(jnp.float32), win, point)


def make(config: dict, traffic: dict, seed: int) -> dict:
    """Arrays of one run.  Device arrays: ``x``/``ivs`` (indexed rows).
    Host arrays: ``qv``/``qint``/``qflt``/``qflag`` (query pool: vectors,
    windows, filter direction and its API flag), ``qsem`` (semantics index
    into :data:`SEMANTICS`), and ``ref_x``/``ref_iv`` (host copies of the
    indexed rows, for the reference)."""
    n, dim = int(config["rows"]), int(config["dim"])
    nq = int(traffic["query_pool"])
    win = traffic["windows"]
    x, ivs, qwin, qpoint = _generate(
        seed_key(seed), n=n, n_query=nq, dim=dim,
        clusters=int(config["clusters"]), std=float(config["cluster_std"]),
        half_min=float(win["half_min"]), half_max=float(win["half_max"]))
    x_h, ivs_h = np.asarray(x), np.asarray(ivs)
    mix = traffic["semantics"]
    sems = np.array([SEMANTICS.index(s) for s in mix], np.int32)
    rng = np.random.default_rng(seed)
    # The same share of each semantics for every seed, in a seeded order.
    qsem = rng.permutation(np.resize(sems, nq))
    point = np.array([SEMANTICS[s] in win["point_for"] for s in qsem])
    qint = np.where(point[:, None], np.asarray(qpoint), np.asarray(qwin))
    qflt = np.array([FILTER[SEMANTICS[s]] for s in qsem])
    return dict(
        x=x[:n], ivs=ivs[:n],
        qv=x_h[n:], qint=qint.astype(np.float32), qsem=qsem,
        qflt=qflt, qflag=np.where(qflt, FLAG_IF, FLAG_IS).astype(np.int32),
        ref_x=x_h[:n], ref_iv=ivs_h[:n],
    )
