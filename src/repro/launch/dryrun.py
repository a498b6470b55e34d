import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This file MUST set XLA_FLAGS before any jax import (done above) — jax
locks the device count on first init.  512 placeholder host devices cover
both the single-pod (16,16)=256 and multi-pod (2,16,16)=512 meshes.

Per cell it emits a JSON record with:
  * memory_analysis (bytes per device: args/outputs/temps/peak)
  * cost_analysis   (HLO flops / bytes accessed, per device under SPMD)
  * collective bytes parsed from the optimized HLO (per collective kind)
  * roofline terms (launch/roofline.py) + MODEL_FLOPS ratio
  * lower/compile wall times

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

import repro.configs as configs
from repro.launch import analytic
from repro.launch import mesh as mesh_lib
from repro.launch import roofline
from repro.models import lm
from repro.models.config import SHAPES
from repro.sharding import rules as rules_lib
from repro.train.step import TrainConfig, make_train_step

# long_500k requires sub-quadratic attention: run for SSM/hybrid and the
# local+global alternating gemma family (O(seq) decode against a sharded
# cache, window-bounded local layers); skip for pure full-attention archs
# and whisper (decoder context is architecturally bounded).
LONG_OK = {"gemma2-27b", "gemma3-12b", "mamba2-780m", "hymba-1.5b"}


def cell_is_skipped(arch: str, shape: str) -> bool:
    return shape == "long_500k" and arch not in LONG_OK


def _spec(axes, rules, mesh):
    return NamedSharding(mesh, rules_lib.resolve_spec(axes, rules, mesh))


def _tree_specs(axes_tree, rules, mesh):
    return jax.tree.map(
        lambda a: _spec(a, rules, mesh), axes_tree,
        is_leaf=rules_lib.is_axes_leaf,
    )


def _bf16(tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape,
            jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype,
        ),
        tree,
    )


def microbatches_for(shape, mesh) -> int:
    """Bound per-microbatch rows-per-device to <=2 (activation/logit peaks)."""
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    rows = max(1, shape.global_batch // dp)
    return max(1, rows // 2)


def build_train_cell(api, shape, mesh, variant="baseline"):
    rules = rules_lib.TRAIN_RULES
    vals, axes = api.abstract()
    mb = microbatches_for(shape, mesh)
    if variant == "opt":
        # §Perf C1+C2+C3: bf16 gathers, half the microbatches (half the
        # per-step param re-gathers), grads pinned to param shardings
        # (reduce-scatter, not replicated all-reduce)
        tcfg = TrainConfig(microbatches=max(1, mb // 2),
                           cast_params_bf16=True)
        step, opt_init = make_train_step(api.loss_fn, tcfg, rules, mesh,
                                         param_axes=axes)
    else:
        tcfg = TrainConfig(microbatches=mb)
        step, opt_init = make_train_step(api.loss_fn, tcfg, rules, mesh)
    opt_abs = jax.eval_shape(opt_init, vals)

    p_sh = _tree_specs(axes, rules, mesh)
    scalar = NamedSharding(mesh, PartitionSpec())
    opt_sh = {"m": p_sh, "v": p_sh, "count": scalar}
    b_axes = api.input_axes()
    batch_specs = api.input_specs(shape)
    b_sh = {k: _spec(b_axes[k], rules, mesh) for k in batch_specs}

    jitted = jax.jit(
        step,
        in_shardings=(p_sh, opt_sh, b_sh, scalar),
        out_shardings=(p_sh, opt_sh, None),
    )
    args = (vals, opt_abs, batch_specs, jax.ShapeDtypeStruct((), jnp.int32))
    layers = api.cfg.n_layers + getattr(api.cfg, "encoder_layers", 0)
    return jitted, args, {
        "microbatches": tcfg.microbatches,
        # scan bodies are listed once in HLO; structurally known trips:
        "scan_multiplier": layers * tcfg.microbatches,
    }


def build_prefill_cell(api, shape, mesh, variant="baseline"):
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    rules = (rules_lib.SERVE_RULES if shape.global_batch >= dp
             else rules_lib.LONG_CONTEXT_SERVE_RULES)
    vals, axes = api.abstract()
    vals = _bf16(vals)
    p_sh = _tree_specs(axes, rules, mesh)
    b_axes = api.input_axes()
    batch_specs = api.input_specs(shape)
    b_sh = {k: _spec(b_axes[k], rules, mesh) for k in batch_specs}

    def prefill(values, batch):
        from repro.sharding.activation import activation_sharding

        with activation_sharding(rules, mesh):
            return api.prefill_fn(values, batch)

    jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh))
    layers = api.cfg.n_layers + getattr(api.cfg, "encoder_layers", 0)
    return jitted, (vals, batch_specs), {
        "rules": "serve",
        "scan_multiplier": layers,
    }


def build_decode_cell(api, shape, mesh, variant="baseline"):
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    long_ctx = shape.global_batch < dp
    rules = (rules_lib.LONG_CONTEXT_SERVE_RULES if long_ctx
             else rules_lib.SERVE_RULES)
    if variant == "opt" and not long_ctx:
        rules = rules_lib.DECODE_SP_RULES  # §Perf: cache seq over model
    vals, axes = api.abstract()
    vals = _bf16(vals)
    p_sh = _tree_specs(axes, rules, mesh)
    scalar = NamedSharding(mesh, PartitionSpec())

    B, S = shape.global_batch, shape.seq_len
    cache_specs = api.decode_cache_specs(B, S)
    cache_axes = api.decode_cache_axes(B, S)
    c_sh = jax.tree.map(
        lambda a: _spec(a, rules, mesh), cache_axes,
        is_leaf=rules_lib.is_axes_leaf,
    )
    tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = _spec(("batch", None), rules, mesh)

    def decode(values, caches, token, pos):
        from repro.sharding.activation import activation_sharding

        with activation_sharding(rules, mesh):
            return api.decode_fn(values, caches, token, pos)

    # donate caches: decode updates them in place (without donation XLA
    # holds input AND output caches + per-layer copies — §Perf dbrx cell)
    donate = (1,) if variant == "opt" else ()
    jitted = jax.jit(decode, in_shardings=(p_sh, c_sh, tok_sh, scalar),
                     donate_argnums=donate)
    args = (vals, cache_specs, tok_spec,
            jax.ShapeDtypeStruct((), jnp.int32))
    return jitted, args, {
        "rules": "long_ctx" if long_ctx else "serve",
        "scan_multiplier": 1,  # decode unrolls layers in python
    }


def run_asdr_cell(shape_name: str, multi_pod: bool, variant="baseline"):
    """The paper's own model (ingp-asdr) as extra dry-run cells."""
    from repro.launch import asdr_steps

    bundle = configs.get("ingp-asdr")
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    if shape_name == "asdr_render":
        jitted, args, extra = asdr_steps.build_render_cell(
            bundle, mesh, variant=variant)
    elif shape_name == "asdr_train":
        jitted, args, extra = asdr_steps.build_train_cell_ngp(bundle, mesh)
    elif shape_name == "render_serve":
        # the serving engine's pooled multi-view march as a mesh cell, so
        # render-serve rows land in the EXPERIMENTS tables next to the LM
        # cells (same JSON record schema)
        from repro.launch import render_serve
        jitted, args, extra = render_serve.build_pooled_march_cell(
            bundle, mesh)
    else:
        raise ValueError(shape_name)

    t0 = time.time()
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = roofline.collective_bytes(
        compiled.as_text(), body_multiplier=extra.get("scan_multiplier", 1))
    flops = float(cost.get("flops", 0.0)) * extra.get("scan_multiplier", 1)
    bts = float(cost.get("bytes accessed", 0.0)) * extra.get(
        "scan_multiplier", 1)
    terms = roofline.roofline_terms(flops, bts, coll["total"])
    n_chips = 512 if multi_pod else 256
    if shape_name == "render_serve":
        # the scene-space block tier's reuse numbers ride along in the
        # serving cell's record: a tiny concrete multi-client run (host
        # devices) reporting cross-client block hit rate, resident bytes
        # vs budget, and evictions — the march-cost AND march-avoided
        # halves of the serving story in one JSON row
        from repro.launch import render_serve as rs_mod
        extra = dict(extra)
        extra["scenecache"] = rs_mod.scenecache_smoke()
    return {
        "arch": "ingp-asdr", "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
        },
        "cost_scan_corrected": {"flops": flops, "bytes": bts},
        "collectives": coll, "roofline": terms,
        "useful_flops_ratio": 1.0,
        **extra,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             variant: str = "baseline"):
    if arch == "ingp-asdr":
        return run_asdr_cell(shape_name, multi_pod, variant)
    shape = SHAPES[shape_name]
    cfg = configs.get(arch)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    api = lm.build(cfg)

    builders = {
        "train": build_train_cell,
        "prefill": build_prefill_cell,
        "decode": build_decode_cell,
    }
    jitted, args, extra = builders[shape.kind](api, shape, mesh,
                                               variant=variant)

    t0 = time.time()
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    mult = extra.get("scan_multiplier", 1)
    coll = roofline.collective_bytes(hlo, body_multiplier=mult)

    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v

    flops_raw = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))
    # scan-corrected HLO terms (bodies listed once; see analytic.py)
    flops_hlo = flops_raw * mult
    bytes_hlo = bytes_raw * mult

    # analytic executed-FLOPs model (exact trip counts, incl. remat)
    an_f = analytic.cell_flops(cfg, shape)
    an_b = analytic.cell_hbm_bytes(cfg, shape, extra.get("microbatches", 1))
    an_flops_chip = an_f["total_flops"] / n_chips
    an_bytes_chip = an_b["total_bytes"] / n_chips

    terms = roofline.roofline_terms(an_flops_chip, an_bytes_chip,
                                    coll["total"])
    terms_hlo = roofline.roofline_terms(flops_hlo, bytes_hlo, coll["total"])

    tokens = shape.global_batch * (
        shape.seq_len if shape.kind != "decode" else 1
    )
    mf = roofline.model_flops(cfg, tokens, shape.kind)
    mf_per_chip = mf / n_chips

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes",
                                  mem.temp_size_in_bytes),
        },
        "cost_raw": {"flops": flops_raw, "bytes_accessed": bytes_raw},
        "cost_scan_corrected": {"flops": flops_hlo, "bytes": bytes_hlo},
        "analytic": {**an_f, **an_b},
        "collectives": coll,
        "roofline": terms,            # analytic flops/bytes + HLO collectives
        "roofline_hlo": terms_hlo,    # scan-corrected HLO flops/bytes
        "model_flops_per_chip": mf_per_chip,
        "useful_flops_ratio": (mf_per_chip / an_flops_chip)
                              if an_flops_chip else 0.0,
        **extra,
    }
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt"],
                    help="opt = §Perf hillclimb configuration")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = configs.list_archs() if (args.all or not args.arch) else [args.arch]

    def shapes_for(arch):
        # ingp-asdr has its own shape set — pairing it with the LM SHAPES
        # (as a naive product would under --all) makes every cell error
        if arch == "ingp-asdr":
            return (["asdr_render", "asdr_train", "render_serve"]
                    if not args.shape else [args.shape])
        return list(SHAPES) if (args.all or not args.shape) else [args.shape]

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes_for(a):
            for m in meshes:
                cells.append((a, s, m))

    suffix = "" if args.variant == "baseline" else f"_{args.variant}"
    for arch, shape_name, mesh_kind in cells:
        tag = f"{arch}_{shape_name}_{mesh_kind}{suffix}"
        out_path = outdir / f"{tag}.json"
        if out_path.exists():
            print(f"[skip-done] {tag}")
            continue
        if cell_is_skipped(arch, shape_name):
            out_path.write_text(json.dumps(
                {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "skipped": True,
                 "reason": "long_500k needs sub-quadratic attention "
                           "(see DESIGN.md)"}, indent=1))
            print(f"[skip] {tag}: full-attention arch")
            continue
        print(f"[run ] {tag} ...", flush=True)
        try:
            rec = run_cell(arch, shape_name, mesh_kind == "multi",
                           variant=args.variant)
            rec["variant"] = args.variant
            out_path.write_text(json.dumps(rec, indent=1))
            r = rec["roofline"]
            print(
                f"[ok  ] {tag}: compile {rec['compile_s']}s "
                f"compute {r['compute_s']:.4f}s memory {r['memory_s']:.4f}s "
                f"coll {r['collective_s']:.4f}s -> {r['bottleneck']}",
                flush=True,
            )
        except Exception as e:  # noqa
            err = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                   "error": str(e)[:2000],
                   "traceback": traceback.format_exc()[-4000:]}
            (outdir / f"{tag}.error.json").write_text(json.dumps(err, indent=1))
            print(f"[FAIL] {tag}: {str(e)[:200]}", flush=True)


if __name__ == "__main__":
    main()
