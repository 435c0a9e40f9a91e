"""Mixture-of-Experts FFN: top-k router + shard-local capacity dispatch.

The dispatch is grouped by data shard so every sort/scatter stays local
under SPMD: tokens are reshaped ``(T,) -> (G, T/G)`` with ``G`` = the
data-parallel degree and the group axis pinned to the data axes — a global
argsort over tokens would otherwise become a cross-device sort (measured:
11 TB of collectives per step on qwen3-moe before this reformulation).

Expert compute runs as a ``lax.scan`` over expert blocks (block axis sharded
over the ``model`` axis) so the transient dispatch buffers are bounded by
``E/blocks`` regardless of expert count; the only cross-model traffic is the
one combine all-reduce per layer (activation-sized, same as dense TP).

Router aux loss follows Switch (load-balance: E · Σ_e f_e · p_e).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import shard_ctx
from repro.models.common import ModelConfig


def build_moe_params(cfg: ModelConfig, b, prefix_layers: bool = True):
    L = (cfg.n_layers,) if prefix_layers else ()
    lax_ = ("layers",) if prefix_layers else ()
    dff = cfg.moe_d_ff or cfg.d_ff
    p = {
        "router": b(L + (cfg.d_model, cfg.n_experts), lax_ + ("embed", "expert")),
        "experts": {
            "w_gate": b(L + (cfg.n_experts, cfg.d_model, dff), lax_ + ("expert", "embed", "mlp")),
            "w_up": b(L + (cfg.n_experts, cfg.d_model, dff), lax_ + ("expert", "embed", "mlp")),
            "w_down": b(L + (cfg.n_experts, dff, cfg.d_model), lax_ + ("expert", "mlp", "embed")),
        },
    }
    if cfg.n_shared_experts:
        sdff = dff * cfg.n_shared_experts
        p["shared"] = {
            "w_gate": b(L + (cfg.d_model, sdff), lax_ + ("embed", "mlp")),
            "w_up": b(L + (cfg.d_model, sdff), lax_ + ("embed", "mlp")),
            "w_down": b(L + (sdff, cfg.d_model), lax_ + ("mlp", "embed")),
        }
    return p


def _local_dispatch(xt, gate_idx, gate_vals, E: int, C: int):
    """Sort-based capacity dispatch over one token block (pure local math).

    xt (T, d); gate_idx/vals (T, K).  Returns (buf (E, C, d),
    t_of_slot (E, C), w_of_slot (E, C)) — slot maps for the combine.
    """
    T, K = gate_idx.shape
    N = T * K
    flat_e = gate_idx.reshape(N)
    flat_t = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[:, None], (T, K)
    ).reshape(N)
    flat_w = gate_vals.reshape(N)
    order = jnp.argsort(flat_e, stable=True)
    e_s = flat_e[order]
    t_s = flat_t[order]
    w_s = flat_w[order]
    first = jnp.searchsorted(e_s, e_s, side="left")
    rank = (jnp.arange(N, dtype=jnp.int32) - first).astype(jnp.int32)
    keep = rank < C
    e_ix = jnp.where(keep, e_s, E)
    r_ix = jnp.where(keep, rank, 0)
    buf = jnp.zeros((E + 1, C, xt.shape[-1]), xt.dtype)
    buf = buf.at[e_ix, r_ix].set(xt[t_s], mode="drop")[:E]
    t_of = jnp.zeros((E + 1, C), jnp.int32).at[e_ix, r_ix].set(t_s, mode="drop")[:E]
    w_of = jnp.zeros((E + 1, C), jnp.float32).at[e_ix, r_ix].set(
        jnp.where(keep, w_s, 0.0), mode="drop"
    )[:E]
    return buf, t_of, w_of


def _router(cfg: ModelConfig, xt, router_w):
    """Top-k routing + Switch aux terms.  xt (T, d), router_w (d, E)."""
    logits = jnp.einsum(
        "td,de->te", xt.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    frac = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], cfg.n_experts, dtype=jnp.float32), axis=0
    )
    mean_p = jnp.mean(probs, axis=0)
    return gate_idx, gate_vals, frac, mean_p


def _moe_ffn_ep(cfg: ModelConfig, p, x: jnp.ndarray):
    """Expert-parallel MoE via shard_map (DESIGN.md §4; the EP baseline).

    Tokens arrive (batch × sequence)-sharded over every mesh axis — the
    residual stream is already (dp, tp)-sharded — so each device dispatches
    only its own tokens; two all-to-alls over the ``model`` axis move token
    slots to/from expert owners; expert weights' fsdp shards are all-gathered
    once per layer.  Measured vs the auto-SPMD global dispatch this is a
    ~50× collective-byte reduction (EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    mesh = shard_ctx._MESH
    axes = set(mesh.axis_names)
    dp_axes = tuple(a for a in ("pod", "data") if a in axes)
    tp_axes = ("model",) if "model" in axes else ()
    all_axes = dp_axes + tp_axes
    sizes = dict(mesh.shape)
    tp = sizes.get("model", 1)
    n_shards = 1
    for a in all_axes:
        n_shards *= sizes[a]

    B, S, d = x.shape
    T = B * S
    T_dev = T // n_shards
    E, K = cfg.n_experts, cfg.top_k
    dff = cfg.moe_d_ff or cfg.d_ff
    E_loc = E // tp
    C = min(max(int(T_dev * K / max(E, 1) * cfg.capacity_factor) + 1, 4), T_dev * K)

    has_shared = bool(cfg.n_shared_experts)

    def local_fn(x_l, router_l, wg_l, wu_l, wd_l, *shared_l):
        # x_l is exactly this device's residual shard (B_loc, S_loc, d):
        # the block stream is (dp, tp)-sharded, so entering the MoE costs
        # zero data movement.
        B_loc, S_loc, _ = x_l.shape
        xt_l = x_l.reshape(B_loc * S_loc, d)                    # (T_dev, d)
        router_w = router_l
        if dp_axes:
            router_w = jax.lax.all_gather(router_w, dp_axes, axis=0, tiled=True)
        if tp > 1:
            router_w = jax.lax.all_gather(router_w, "model", axis=1, tiled=True)

        gate_idx, gate_vals, frac, mean_p = _router(cfg, xt_l, router_w)
        aux_f = jax.lax.pmean(frac, all_axes)
        aux_p = jax.lax.pmean(mean_p, all_axes)
        aux = E * jnp.sum(aux_f * aux_p) * cfg.router_aux_weight

        buf, t_of, w_of = _local_dispatch(xt_l, gate_idx, gate_vals, E, C)

        # ---- all-to-all: send expert slices to their owners ----
        if tp > 1:
            send = buf.reshape(tp, E_loc, C, d)
            recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0)
            tok_in = jnp.moveaxis(recv, 0, 1).reshape(E_loc, tp * C, d)
        else:
            tok_in = buf

        # ---- expert FFN (weights' fsdp shards gathered once) ----
        wg = jax.lax.all_gather(wg_l, dp_axes, axis=1, tiled=True) if dp_axes else wg_l
        wu = jax.lax.all_gather(wu_l, dp_axes, axis=1, tiled=True) if dp_axes else wu_l
        wd = jax.lax.all_gather(wd_l, dp_axes, axis=2, tiled=True) if dp_axes else wd_l
        hg = jnp.einsum("ecd,edf->ecf", tok_in, wg)
        hu = jnp.einsum("ecd,edf->ecf", tok_in, wu)
        h = jax.nn.silu(hg.astype(jnp.float32)).astype(x.dtype) * hu
        y_sl = jnp.einsum("ecf,efd->ecd", h, wd)                # (E_loc, tp*C, d)

        # ---- all-to-all back + local combine ----
        if tp > 1:
            back = jnp.moveaxis(y_sl.reshape(E_loc, tp, C, d), 1, 0)
            mine = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0)
            y_all = mine.reshape(E, C, d)
        else:
            y_all = y_sl
        contrib = y_all * w_of[..., None].astype(x.dtype)
        y_tok = jnp.zeros((B_loc * S_loc, d), x.dtype).at[t_of].add(contrib)

        if has_shared:
            # tokens are split over the model axis too, so every device
            # needs the FULL shared-expert weights for its own tokens (an
            # f-shard + psum would mix different tokens' partials).
            swg, swu, swd = shared_l
            if dp_axes:
                swg = jax.lax.all_gather(swg, dp_axes, axis=0, tiled=True)
                swu = jax.lax.all_gather(swu, dp_axes, axis=0, tiled=True)
                swd = jax.lax.all_gather(swd, dp_axes, axis=1, tiled=True)
            if tp > 1:
                swg = jax.lax.all_gather(swg, "model", axis=1, tiled=True)
                swu = jax.lax.all_gather(swu, "model", axis=1, tiled=True)
                swd = jax.lax.all_gather(swd, "model", axis=0, tiled=True)
            g = jnp.einsum("td,df->tf", xt_l, swg)
            u = jnp.einsum("td,df->tf", xt_l, swu)
            y_tok = y_tok + jnp.einsum(
                "tf,fd->td",
                jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u,
                swd,
            )

        return y_tok.reshape(B_loc, S_loc, d), aux

    row0 = P(dp_axes, "model" if tp > 1 else None, None)   # residual layout
    tp_dim = "model" if tp > 1 else None
    specs_in = [
        row0,                              # x (B, S, d)
        P(dp_axes, tp_dim),                # router (d, E)
        P(tp_dim, dp_axes, None),          # w_gate (E, d, f)
        P(tp_dim, dp_axes, None),          # w_up
        P(tp_dim, None, dp_axes),          # w_down (E, f, d)
    ]
    args = [x, p["router"], p["experts"]["w_gate"], p["experts"]["w_up"],
            p["experts"]["w_down"]]
    if has_shared:
        specs_in += [P(dp_axes, tp_dim), P(dp_axes, tp_dim), P(tp_dim, dp_axes)]
        args += [p["shared"]["w_gate"], p["shared"]["w_up"], p["shared"]["w_down"]]

    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=tuple(specs_in),
        out_specs=(row0, P()), check_vma=False,
    )
    x_in = shard_ctx.constrain(x, ("dp", "tp", None))
    y, aux = fn(x_in, *args[1:])
    # pin the output back to the residual stream's (dp, tp) layout so the
    # gradient accumulate doesn't force an involuntary replication (XLA
    # spmd_partitioner warning otherwise).
    y = shard_ctx.constrain(y, ("dp", "tp", None))
    return y, jnp.mean(aux)


def moe_ffn(cfg: ModelConfig, p, x: jnp.ndarray):
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    if shard_ctx.active():
        B, S, d = x.shape
        n_shards = shard_ctx.dp_size() * shard_ctx.tp_size()
        dpsz, tpsz = shard_ctx.dp_size(), shard_ctx.tp_size()
        if (
            dpsz * tpsz > 1
            and B % max(dpsz, 1) == 0
            and S % max(tpsz, 1) == 0
            and cfg.n_experts % max(tpsz, 1) == 0
            and (B * S) // (dpsz * tpsz) >= 4
        ):
            return _moe_ffn_ep(cfg, p, x)
    return _moe_ffn_local(cfg, p, x)


def _moe_ffn_local(cfg: ModelConfig, p, x: jnp.ndarray):
    """Single-shard (or fallback) path: same math, no collectives."""
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    dff = cfg.moe_d_ff or cfg.d_ff
    G = shard_ctx.dp_size()
    if G <= 0 or T % G:
        G = 1
    Tg = T // G

    xt = shard_ctx.constrain(x.reshape(G, Tg, d), ("dp", None, None))

    logits = jnp.einsum(
        "gtd,de->gte", xt.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)            # (G, Tg, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch aux load-balance loss (global means).
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32), axis=(0, 1)
    )
    aux = E * jnp.sum(dispatch_frac * jnp.mean(probs, axis=(0, 1))) * cfg.router_aux_weight

    # ---- shard-local sort-based dispatch (per group g) ----
    C = min(max(int(Tg * K / max(E, 1) * cfg.capacity_factor) + 1, 4), Tg * K)
    N = Tg * K
    flat_e = gate_idx.reshape(G, N)
    flat_t = jnp.broadcast_to(
        jnp.arange(Tg, dtype=jnp.int32)[:, None], (Tg, K)
    ).reshape(1, N)
    flat_t = jnp.broadcast_to(flat_t, (G, N))
    flat_w = gate_vals.reshape(G, N)

    order = jnp.argsort(flat_e, axis=1, stable=True)
    e_s = jnp.take_along_axis(flat_e, order, axis=1)
    t_s = jnp.take_along_axis(flat_t, order, axis=1)
    w_s = jnp.take_along_axis(flat_w, order, axis=1)
    first = jax.vmap(lambda a: jnp.searchsorted(a, a, side="left"))(e_s)
    rank = jnp.arange(N, dtype=jnp.int32)[None, :] - first.astype(jnp.int32)
    keep = rank < C
    slot = jnp.where(keep, e_s * C + rank, E * C)            # E*C = drop bin
    g_ix = jnp.arange(G)[:, None]

    # Dispatch into (G, E, C, d) with the EXPERT axis model-sharded: the
    # tokens (replicated along the model axis within their data group) are
    # scattered by every model shard into just its expert slice — no
    # cross-shard dispatch traffic; XLA masks out-of-shard updates locally.
    e_ix = jnp.where(keep, e_s, E)
    r_ix = jnp.where(keep, rank, 0)
    x_sorted = jnp.take_along_axis(xt, t_s[..., None], axis=1)   # (G, N, d)
    g_ix3 = jnp.broadcast_to(g_ix, e_ix.shape)
    buf = jnp.zeros((G, E + 1, C, d), x.dtype)
    buf = buf.at[g_ix3, e_ix, r_ix].set(x_sorted, mode="drop")[:, :E]
    buf = shard_ctx.constrain(buf, ("dp", "tp", None, None))

    # slot -> (token, combine weight) inverse maps for the combine scatter
    t_of_slot = jnp.zeros((G, E + 1, C), jnp.int32).at[g_ix3, e_ix, r_ix].set(
        t_s, mode="drop"
    )[:, :E]
    w_of_slot = jnp.zeros((G, E + 1, C), jnp.float32).at[g_ix3, e_ix, r_ix].set(
        jnp.where(keep, w_s, 0.0), mode="drop"
    )[:, :E]

    # ---- expert compute: all experts at once, expert axis sharded ----
    hg = jnp.einsum("gecd,edf->gecf", buf, p["experts"]["w_gate"])
    hu = jnp.einsum("gecd,edf->gecf", buf, p["experts"]["w_up"])
    h = jax.nn.silu(hg.astype(jnp.float32)).astype(x.dtype) * hu
    y_slots = jnp.einsum("gecf,efd->gecd", h, p["experts"]["w_down"])
    y_slots = shard_ctx.constrain(y_slots, ("dp", "tp", None, None))

    # ---- combine: weighted scatter-add back to token order (one AR) ----
    contrib = y_slots * w_of_slot[..., None].astype(x.dtype)
    g_full = jnp.broadcast_to(jnp.arange(G)[:, None, None], t_of_slot.shape)
    y = jnp.zeros((G, Tg, d), x.dtype).at[g_full, t_of_slot].add(contrib)
    y = shard_ctx.constrain(y, ("dp", None, None))

    if cfg.n_shared_experts:
        sp = p["shared"]
        g = jnp.einsum("gtd,df->gtf", xt, sp["w_gate"])
        u = jnp.einsum("gtd,df->gtf", xt, sp["w_up"])
        y = y + jnp.einsum(
            "gtf,fd->gtd",
            jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u,
            sp["w_down"],
        )
    return y.reshape(B, S, d), aux
