"""Fused-march benchmark: single-kernel Phase II vs the chunked reference.

  PYTHONPATH=src python benchmarks/fused_march.py [--quick] [--smoke]

Four sections, appending JSON rows to out/bench/fused_march.json and
(full runs) writing the canonical summary to BENCH_fused_march.json at
the repo root:

  * replay — a short trained-NGP trajectory marches its Phase-II blocks
    through BOTH backends (the serving pool's jitted batched march, so
    this times exactly what the engine launches).  Gates:
      - per-frame |PSNR(ref) - PSNR(fused)| vs the fixed-96 baseline
        <= 0.1 dB (the backend-seam quality contract),
      - chunks_done identical on every frame (early-termination parity),
      - fused speedup >= 1.0x on the marched wall time.
  * full-config — the production table stack (16 x 2^19 x 2 = 64 MB)
    under the STREAMED fused backend, which the resident path cannot
    run (its VMEM ask is gated and the resident pin must refuse).
    Gates: resident refused, streamed speedup >= 2x over the chunked
    reference, psnr delta <= 0.1 dB vs a dense-budget baseline, chunks
    AND per-ray chunks exactly equal.
  * per-ray-exit — a saturating block through pool.collect with
    ``per_ray_early_exit`` on: the gated ``ray_exit_samples_skipped``
    counter must show skipped sample work at unchanged chunk counters.
  * engine — a >=8-slot serving run with the fused backend and
    inflight_batches >= 2.  Gate: some round launched > 1 batch
    (the streaming scheduler actually fills idle dispatch slots).

``--smoke`` (nightly CI) runs only the replay gates at one small frame:
chunks parity + the 0.1 dB ceiling, no root summary rewrite.

The trained model (not the analytic field) exercises the real kernel
path: hash tables + padded MLP stacks in the fused kernel; the
full-config section uses random-init weights (training the 64 MB grid
is out of scope on CPU — the streaming contract is table-SIZE-driven).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from common import baseline_image, emit_rows, serve_bench_acfg, trained_model
from repro.core import model as model_lib, pipeline, rendering, scene
from repro.kernels import ops
from repro.serve import pool as pool_lib, stats as stats_lib
from repro.serve.render_engine import (RenderRequest, RenderServeConfig,
                                       RenderServingEngine)

MAX_PSNR_DELTA_DB = 0.1
BENCH_PATH = Path(__file__).resolve().parent.parent / "BENCH_fused_march.json"


def _frame_blocks(fns, acfg, cam):
    """One pose's Phase-II block tensors (o_b, d_b, budgets, order, R)."""
    o, d = scene.camera_rays(cam)
    counts, _ = pipeline.probe_phase(fns, acfg, cam)
    o, d, counts, _, _ = pipeline.pad_rays_to_blocks(acfg, o, d, counts)
    order, budgets = pipeline.block_sort(acfg, counts)
    B = acfg.block_size
    return (o[order].reshape(-1, B, 3), d[order].reshape(-1, B, 3),
            budgets, order, cam.height * cam.width)


def _image(rgb_s, order, R, hw):
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return np.asarray(rgb_s.reshape(-1, 3)[inv][:R].reshape(*hw, 3))


def replay_section(args):
    params, cfg = trained_model("lego", quick=args.quick)
    fns = ops.field_fns(params, cfg)
    acfg_r = serve_bench_acfg(block=args.block)
    acfg_f = dataclasses.replace(acfg_r, march_backend="fused")
    cams = [scene.look_at_camera(args.size, args.size,
                                 theta=0.9 + 0.08 * i, phi=0.55)
            for i in range(args.frames)]

    march_r = pool_lib.batched_march(fns, acfg_r)
    march_f = pool_lib.batched_march(fns, acfg_f)
    rows, t_ref = [], {"reference": 0.0, "fused": 0.0}
    worst = 0.0
    for fi, cam in enumerate(cams):
        o_b, d_b, budgets, order, R = _frame_blocks(fns, acfg_r, cam)
        outs, times = {}, {}
        for name, march in [("reference", march_r), ("fused", march_f)]:
            jax.block_until_ready(march(o_b, d_b, budgets))  # compile warm
            t0 = time.time()
            outs[name] = jax.block_until_ready(march(o_b, d_b, budgets))
            times[name] = (time.time() - t0) * 1e3
            t_ref[name] += times[name]
        assert np.array_equal(np.asarray(outs["reference"][3]),
                              np.asarray(outs["fused"][3])), (
            f"frame {fi}: chunks_done diverged")
        hw = (cam.height, cam.width)
        base = jnp.asarray(baseline_image(fns, cam))
        img_r = _image(outs["reference"][0], order, R, hw)
        img_f = _image(outs["fused"][0], order, R, hw)
        p_r = float(rendering.psnr(jnp.asarray(img_r), base))
        p_f = float(rendering.psnr(jnp.asarray(img_f), base))
        worst = max(worst, abs(p_r - p_f))
        print(f"  frame {fi}: ref {times['reference']:7.1f}ms "
              f"fused {times['fused']:7.1f}ms  "
              f"psnr {p_r:.2f}/{p_f:.2f} dB (|d|={abs(p_r - p_f):.4f})")
        rows.append(dict(bench="fused_march", mode="replay", frame=fi,
                         ref_ms=times["reference"], fused_ms=times["fused"],
                         psnr_ref_db=p_r, psnr_fused_db=p_f,
                         n_blocks=int(o_b.shape[0])))
    speedup = t_ref["reference"] / max(t_ref["fused"], 1e-9)
    print(f"  total: ref {t_ref['reference']:.0f}ms fused "
          f"{t_ref['fused']:.0f}ms -> {speedup:.2f}x, "
          f"worst |psnr delta| {worst:.4f} dB")
    assert worst <= MAX_PSNR_DELTA_DB, (
        f"GATE: fused psnr delta {worst:.4f} dB > {MAX_PSNR_DELTA_DB}")
    assert speedup >= 1.0, f"GATE: fused speedup {speedup:.2f}x < 1.0x"
    rows.append(dict(bench="fused_march", mode="replay_summary",
                     speedup=speedup, worst_psnr_delta_db=worst,
                     gate_ok=True))
    return rows, fns


def full_config_section(args):
    """The tentpole gate: the FULL 16 x 2^19 x 2 table stack (64 MB)
    marches under the streamed fused backend at >= 2x the chunked
    reference; the resident path must REFUSE the config outright."""
    cfg = model_lib.NGPConfig.make()          # production sizes
    params = model_lib.init_ngp(jax.random.PRNGKey(7), cfg)
    fns = ops.field_fns(params, cfg)
    res = fns.fused
    acfg_r = serve_bench_acfg(block=128)
    acfg_f = dataclasses.replace(acfg_r, march_backend="fused")
    vmem = dict(
        resident=ops.fused_march_vmem_bytes(acfg_f, res, streamed=False),
        streamed=ops.fused_march_vmem_bytes(acfg_f, res, streamed=True),
        limit=ops.FUSED_MARCH_VMEM_LIMIT)
    assert vmem["resident"] > vmem["limit"] >= vmem["streamed"], vmem
    assert ops._select_streaming(acfg_f, res)  # auto resolves to streamed
    try:
        ops._select_streaming(dataclasses.replace(
            acfg_f, march_table_streaming="resident"), res)
        resident_refused = False
    except ValueError:
        resident_refused = True
    assert resident_refused, "resident pin accepted a 64 MB stack"
    print(f"  vmem: resident {vmem['resident'] / 2**20:.0f} MB > limit "
          f"{vmem['limit'] / 2**20:.0f} MB >= streamed "
          f"{vmem['streamed'] / 2**20:.0f} MB (resident REFUSED)")

    cam = scene.look_at_camera(16, 16, theta=0.9, phi=0.55)
    o, d = scene.camera_rays(cam)
    B = acfg_f.block_size
    o_b = o.reshape(-1, B, 3)
    d_b = d.reshape(-1, B, 3)
    budgets = jnp.asarray([48, 32], jnp.int32)

    march_r = pool_lib.batched_march(fns, acfg_r)
    march_f = pool_lib.batched_march(fns, acfg_f)
    outs, times = {}, {}
    for name, march in [("reference", march_r), ("fused", march_f)]:
        jax.block_until_ready(march(o_b, d_b, budgets))    # compile warm
        t0 = time.time()
        outs[name] = jax.block_until_ready(march(o_b, d_b, budgets))
        times[name] = (time.time() - t0) * 1e3
    assert np.array_equal(np.asarray(outs["reference"][3]),
                          np.asarray(outs["fused"][3])), "chunks diverged"
    assert np.array_equal(np.asarray(outs["reference"][4]),
                          np.asarray(outs["fused"][4])), (
        "per-ray chunks diverged")
    # quality vs a dense-budget reference march (the dB contract): both
    # adaptive backends scored against the same budget-96 render
    base = jax.block_until_ready(
        march_r(o_b, d_b, jnp.full((2,), 96, jnp.int32)))
    base_rgb = jnp.asarray(np.asarray(base[0]))
    p_r = float(rendering.psnr(outs["reference"][0], base_rgb))
    p_f = float(rendering.psnr(outs["fused"][0], base_rgb))
    delta = abs(p_r - p_f)
    speedup = times["reference"] / max(times["fused"], 1e-9)
    print(f"  full config: ref {times['reference']:.0f}ms streamed-fused "
          f"{times['fused']:.0f}ms -> {speedup:.2f}x, psnr "
          f"{p_r:.2f}/{p_f:.2f} dB (|d|={delta:.4f})")
    assert delta <= MAX_PSNR_DELTA_DB, f"GATE: {delta:.4f} dB"
    assert speedup >= 2.0, (
        f"GATE: full-config streamed speedup {speedup:.2f}x < 2.0x")
    row = dict(bench="fused_march", mode="full_config", backend="streamed",
               config=f"{cfg.grid.n_levels}x2^{cfg.grid.log2_table_size}"
                      f"x{cfg.grid.feature_dim}",
               table_mb=round(int(np.prod(res.tables.shape)) * 4 / 2**20),
               ref_ms=times["reference"], fused_ms=times["fused"],
               speedup=speedup, psnr_delta_db=delta, chunks_parity=True,
               ray_chunks_parity=True, resident_refused=resident_refused,
               fused_march_vmem_bytes=vmem, gate_ok=True)
    return [row]


def per_ray_exit_section(args):
    """Saturating block through the REAL pool.collect path: the gated
    ``ray_exit_samples_skipped`` counter must price skipped sample work
    while both chunk counters stay exactly equal to the flag-off run."""
    cfg = model_lib.NGPConfig.small()
    params = model_lib.init_ngp(jax.random.PRNGKey(0), cfg)
    hot = dict(params)
    hot["grid"] = jnp.abs(params["grid"]) + 0.5
    hot["mlps"] = dict(params["mlps"])
    hot["mlps"]["density"] = [jnp.abs(w) * 4.0
                              for w in params["mlps"]["density"]]
    fns = ops.field_fns(hot, cfg)
    B = 64
    # half the rays bore into the saturating cube, half graze past it —
    # the block rides its full budget while the hot rays exit early
    o_hit = jnp.tile(jnp.asarray([0.45, 0.45, -0.3]), (B // 2, 1))
    o_hit = o_hit + jnp.linspace(0.0, 0.1, B // 2)[:, None] * jnp.asarray(
        [1.0, 1.0, 0.0])
    o_miss = jnp.tile(jnp.asarray([0.5, 0.5, -2.0]), (B // 2, 1))
    o_b = jnp.concatenate([o_hit, o_miss])[None]
    d_b = jnp.tile(jnp.asarray([0.0, 0.0, 1.0]), (1, B, 1))
    budgets = jnp.asarray([192], jnp.int32)

    base = dataclasses.replace(serve_bench_acfg(block=B),
                               march_backend="fused")
    acfg_on = dataclasses.replace(base, per_ray_early_exit=True)
    out_off = ops.fused_march_blocks(fns.fused, base, o_b, d_b, budgets)
    out_on = ops.fused_march_blocks(fns.fused, acfg_on, o_b, d_b, budgets)
    assert np.array_equal(np.asarray(out_off[3]), np.asarray(out_on[3]))
    assert np.array_equal(np.asarray(out_off[4]), np.asarray(out_on[4]))

    class _Req:
        rid, scene = 0, "bench"

    class _Slot:
        req = _Req()

        def deliver(self, *a, **kw):
            pass

    for name, acfg in [("off", base), ("on", acfg_on)]:
        counters = stats_lib.EngineCounters()
        pool = pool_lib.BlockPool(acfg, 1, None, counters)
        out = ops.fused_march_blocks(fns.fused, acfg, o_b, d_b, budgets)
        pool.collect(([(_Slot(), 0, None, None, 192, None, None, False)],
                      [], 0, out, 1))
        skipped = counters.ray_exit_samples_skipped
        if name == "off":
            assert skipped == 0, "counter must stay gated off"
        else:
            assert skipped > 0, "no sample work skipped on saturation"
    chunks = int(np.asarray(out_on[3])[0])
    total = chunks * B * base.chunk
    print(f"  per-ray exit: {skipped}/{total} samples skipped "
          f"({skipped / total:.0%}) at exact chunk parity")
    return [dict(bench="fused_march", mode="per_ray_exit",
                 samples_skipped=int(skipped), block_samples=total,
                 skipped_fraction=skipped / total, chunks_parity=True,
                 gate_ok=True)]


def engine_section(args, fns):
    acfg = dataclasses.replace(serve_bench_acfg(block=64),
                               march_backend="fused")
    eng = RenderServingEngine({"lego": fns}, acfg, RenderServeConfig(
        slots=max(args.slots, 8), blocks_per_batch=4, reuse=None,
        inflight_batches=max(args.inflight, 2)))
    reqs = [RenderRequest(rid=i, scene="lego",
                          cam=scene.look_at_camera(
                              32, 32, theta=0.9 + 0.05 * i, phi=0.55))
            for i in range(max(args.slots, 8))]
    t0 = time.time()
    eng.render(reqs)
    wall = time.time() - t0
    st = eng.engine_stats()
    hist = st["batches_per_round"]
    print(f"  engine: {len(reqs)} frames in {wall:.2f}s, "
          f"march p50 {st['march_ms_p50']:.1f}ms, "
          f"batches/round {hist}")
    assert hist and max(hist) > 1, (
        f"GATE: no multi-batch rounds at {len(reqs)} slots: {hist}")
    return [dict(bench="fused_march", mode="engine", frames=len(reqs),
                 wall_s=wall, march_ms_p50=st["march_ms_p50"],
                 march_ms_p99=st["march_ms_p99"],
                 batches_per_round={str(k): v for k, v in hist.items()},
                 gate_ok=True)]


def write_canonical(rows):
    """BENCH_fused_march.json at the repo root: the one-file perf record
    (latest full run wins; the append-only history stays in out/bench/)."""
    import json
    by_mode = {}
    for r in rows:
        by_mode.setdefault(r["mode"], r)
    summary = {
        "bench": "fused_march",
        "backend": "fused (streamed at full config, resident when fits)",
        "replay": {k: by_mode["replay_summary"][k]
                   for k in ("speedup", "worst_psnr_delta_db", "gate_ok")},
        "full_config": {k: by_mode["full_config"][k]
                        for k in ("config", "table_mb", "speedup",
                                  "psnr_delta_db", "chunks_parity",
                                  "ray_chunks_parity", "resident_refused",
                                  "fused_march_vmem_bytes", "gate_ok")},
        "per_ray_exit": {k: by_mode["per_ray_exit"][k]
                         for k in ("samples_skipped", "skipped_fraction",
                                   "chunks_parity", "gate_ok")},
        "engine": {k: by_mode["engine"][k]
                   for k in ("frames", "march_ms_p50", "batches_per_round",
                             "gate_ok")},
        "chunks_parity": True,
    }
    BENCH_PATH.write_text(json.dumps(summary, indent=1) + "\n")
    print(f"  [json] canonical summary -> {BENCH_PATH}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="nightly CI: replay gates only, one small frame")
    ap.add_argument("--frames", type=int, default=3)
    ap.add_argument("--size", type=int, default=48)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--inflight", type=int, default=2)
    args = ap.parse_args()
    if args.smoke:
        args.quick, args.frames, args.size, args.block = True, 1, 32, 64
    print("[fused-march] replay: reference vs fused backend")
    rows, fns = replay_section(args)
    if args.smoke:
        emit_rows("fused_march", rows)
        print("[fused-march] smoke gates OK (chunks parity + psnr delta)")
        return
    print("[fused-march] full config: streamed tables (64 MB stack)")
    rows += full_config_section(args)
    print("[fused-march] per-ray early exit: gated skip counter")
    rows += per_ray_exit_section(args)
    print("[fused-march] engine: streaming dispatch at "
          f">={max(args.slots, 8)} slots")
    rows += engine_section(args, fns)
    emit_rows("fused_march", rows)
    write_canonical(rows)
    print("[fused-march] all gates OK")


if __name__ == "__main__":
    main()
