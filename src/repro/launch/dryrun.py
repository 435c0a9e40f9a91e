import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input-shape × mesh) cell with ShapeDtypeStruct stand-ins —
no allocation — and record memory analysis, FLOP/byte costs and the
loop-weighted collective bytes for the roofline (EXPERIMENTS.md §Dry-run).

Usage::

    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh multi --out results/dryrun.jsonl
    python -m repro.launch.dryrun --index-cell --mesh single   # the paper's
        sharded UG search step as its own dry-run cell

Exit code != 0 on any failed cell: failures here are sharding bugs.
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCHS, SHAPES, get_arch, input_specs
from repro.launch import shardings as shard_lib
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import shard_ctx
from repro.models.api import get_model
from repro.train import optim


def _sds_tree(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def build_cell(arch_name: str, shape_name: str, mesh, *, moe_a2a: bool = False,
               remat_policy: str | None = None):
    """Returns (fn, example_args, in_shardings, out_shardings)."""
    spec = get_arch(arch_name)
    cfg = spec.config
    if remat_policy is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, remat=remat_policy != "none")
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    pshard = model.shardings(mesh)
    params_sds = model.shapes()
    rep = NamedSharding(mesh, P())

    if shape.kind == "train":
        ocfg = optim.AdamWConfig(
            state_dtype=jnp.bfloat16 if cfg.moe else jnp.float32
        )
        opt_sds = jax.eval_shape(lambda p: optim.init(ocfg, p), params_sds)
        opt_shard = optim.AdamWState(rep, pshard, pshard)
        batch = input_specs(cfg, shape)
        bshard = shard_lib.batch_shardings(mesh)

        def train_step(params, opt_state, b):
            (loss, _), grads = jax.value_and_grad(
                lambda p: model.loss(p, b), has_aux=True
            )(params)
            new_p, new_o, stats = optim.update(ocfg, opt_state, params, grads)
            return new_p, new_o, loss

        return (
            train_step,
            (params_sds, opt_sds, batch),
            (pshard, opt_shard, bshard),
            (pshard, opt_shard, rep),
            (0, 1),   # donate params + opt state (in-place update)
        )

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        bshard = shard_lib.batch_shardings(mesh)

        def prefill_step(params, b):
            hidden, caches = model.prefill(params, b)
            # serving returns last-position logits (next-token readiness)
            from repro.models import transformer as tr

            logits = tr.unembed(cfg, params, hidden[:, -1:, :])
            return logits, caches

        return (prefill_step, (params_sds, batch), (pshard, bshard), None, ())

    # decode
    B, S = shape.global_batch, shape.seq_len
    inputs = input_specs(cfg, shape)
    state_sds, tok_sds = inputs["state"], inputs["tokens"]
    sshard = shard_lib.decode_state_shardings(cfg, mesh, B, S)
    tshard = shard_lib.token_sharding(mesh, B)

    def serve_step(params, state, tokens):
        new_state, logits = model.decode_step(params, state, tokens)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return new_state, next_tok

    return (
        serve_step,
        (params_sds, state_sds, tok_sds),
        (pshard, sshard, tshard),
        (sshard, tshard),
        (1,),     # donate the decode state (in-place cache update)
    )


def build_index_cell(mesh, *, n_global=1 << 20, dim=768, m_deg=64,
                     ef=64, k=10, nq=1024, hierarchical=True):
    """The paper's own technique as a dry-run cell: sharded UG search step."""
    from repro.core import intervals as iv
    from repro.core.sharded import (
        ShardedIndex, make_sharded_search_fn, store_pspecs,
    )
    from repro.core.store import IndexStore, VectorPlane

    index_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fn = make_sharded_search_fn(
        mesh, index_axes=index_axes, sem=iv.Semantics.IF, ef=ef, k=k,
        hierarchical=hierarchical,
    )
    row = NamedSharding(mesh, P(index_axes))
    rep = NamedSharding(mesh, P())
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d)
    store_sds = IndexStore(
        plane=VectorPlane("f32", sds((n_global, dim), jnp.float32)),
        rerank=None,
        intervals=sds((n_global, 2), jnp.float32),
        nbrs=sds((n_global, m_deg), jnp.int32),
        status=sds((n_global, m_deg), jnp.uint8),
        entry=None,
    )
    sidx = ShardedIndex(store_sds, sds((n_global,), jnp.int32))
    args = (
        sidx,
        sds((nq, dim), jnp.float32),           # queries
        sds((nq, 2), jnp.float32),             # query intervals
    )
    sidx_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        ShardedIndex(store_pspecs(store_sds, index_axes), P(index_axes)),
        is_leaf=lambda v: isinstance(v, P),
    )
    shardings = (sidx_shardings, rep, rep)
    return fn, args, shardings, None


def run_cell(arch: str, shape: str, mesh_kind: str, *, index_cell=False,
             moe_a2a=False, verbose=True) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "ok": False,
    }
    try:
        if index_cell:
            fn, args, in_sh, out_sh = build_index_cell(mesh)
            donate = ()
            rec["arch"] = "ug-index-search"
        else:
            spec = get_arch(arch)
            skip = spec.skip_reason(shape)
            if skip:
                rec.update(ok=True, skipped=skip)
                return rec
            fn, args, in_sh, out_sh, donate = build_cell(arch, shape, mesh, moe_a2a=moe_a2a)

        with shard_ctx.use_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        hlo_dir = pathlib.Path("results/hlo")
        hlo_dir.mkdir(parents=True, exist_ok=True)
        import gzip

        tag = f"{rec['arch']}_{shape}_{mesh_kind}".replace("/", "-")
        with gzip.open(hlo_dir / f"{tag}.hlo.gz", "wt") as f:
            f.write(hlo)
        stats = analyze_hlo(hlo)

        rec.update(
            ok=True,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            # loop-weighted analytic numbers (while bodies × trip count);
            # raw cost_analysis kept for cross-checking (visits loops once)
            flops=float(stats.flops),
            bytes_accessed=float(stats.hbm_bytes),
            xla_flops=float(cost.get("flops", 0.0)),
            xla_bytes=float(cost.get("bytes accessed", 0.0)),
            mem=_mem_dict(mem),
            collective_bytes=stats.collectives.total_bytes,
            collective_by_type=stats.collectives.by_type,
            loop_trip_counts={
                k: v for k, v in sorted(stats.collectives.trip_counts.items())[:16]
            },
        )
        if verbose:
            print(f"[dryrun] {rec['arch']} × {shape} × {mesh_kind}: OK "
                  f"(compile {rec['compile_s']}s)")
            print(f"  memory: {rec['mem']}")
            print(f"  flops/device: {rec['flops']:.3e}  "
                  f"bytes/device: {rec['bytes_accessed']:.3e}")
            print(stats.collectives.fmt())
    except Exception as e:  # noqa: BLE001 — failures are the signal here
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[dryrun] {arch} × {shape} × {mesh_kind}: FAIL {rec['error']}")
    return rec


def _mem_dict(mem) -> dict:
    out = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr.replace("_size_in_bytes", "")] = int(v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS) + [None])
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true", help="every (arch × shape)")
    ap.add_argument("--index-cell", action="store_true",
                    help="dry-run the sharded UG search step instead")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args(argv)

    cells = []
    if args.index_cell:
        cells = [(None, "index", args.mesh)]
    elif args.all:
        cells = [(a, s, args.mesh) for a in sorted(ARCHS) for s in SHAPES]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all / --index-cell)")
        cells = [(args.arch, args.shape, args.mesh)]

    failures = 0
    for arch, shape, mesh_kind in cells:
        rec = run_cell(arch or "", shape, mesh_kind, index_cell=args.index_cell)
        if args.out:
            p = pathlib.Path(args.out)
            p.parent.mkdir(parents=True, exist_ok=True)
            with p.open("a") as f:
                f.write(json.dumps(rec) + "\n")
        failures += 0 if rec.get("ok") else 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
