#!/usr/bin/env python3
"""Chip smoke run: the paper's Instant-NGP configuration served on a TPU.

Drives the main path once at full width: ``configs/ingp_asdr.CONFIG``
(16 x 2^19 x 2 hash tables, the paper MLP, 800x800 frames, up to 192
samples, block 4096, chunk 32) with random weights made from a seed, served
through ``RenderServingEngine`` with the default ``RenderServeConfig``
(probe reuse on) and the chunked march.  Every frame is checked against
an independent float32 render on the chip; frame 0, whose probe is fresh,
is also checked tightly against the single-image ASDR pipeline.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # Stage A on chips 1-3, checked
                                     # frame for frame against chip 0 alone

Runs in one process.  Exits non-zero, without a result line, when JAX
finds no TPU or when any check fails.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
N_FRAMES = 4
THETA0, DTHETA, PHI = 0.6, 0.01, 0.5   # nearby poses: 0.57 deg apart
SCENE = "ngp"
# init_ngp draws tables in +-1e-4 (Instant-NGP's init), where the density
# MLP sees an encoding of ~0 and neither the encode nor the density kernel
# would shape the image; scaled to +-1 both shape every frame.
GRID_SCALE = 1e4
REF_RAYS = 512          # rays per block of the float32 reference render
# The reference renders every REF_STRIDE-th row and column of each frame
# (40,000 of 640,000 pixels at 800x800): a full 192-sample frame costs as
# much chip time as the served frame itself.
REF_STRIDE = 4
# The four-chip comparison serves CONFIG's field and ASDR settings at a
# smaller frame.  At 800x800 its eight frames (sync and device engines)
# would march for ~12 minutes on four chips (one v5e encodes ~1.4M
# samples/s); the placement it checks, Stage A on chips 1-3, does not
# depend on the frame size.  At 128x128 a frame is 4 blocks of 4096 rays.
FOUR_CHIP_HW = (128, 128)
FOUR_CHIP_DTHETA = 0.5  # rad between the four-chip poses: no probe reuse
# Floor on each served frame's PSNR against the float32 192-sample
# reference.  ASDR's adaptive sample counts (delta = 1/2048 per probe ray,
# interpolated between probes, reused across poses by warping) and the
# TPU's default-precision float32 matmuls each cost a little.
PSNR_FLOOR_DB = 30.0
# Frame 0 against pipeline.render_asdr_image on the same fields: the same
# probe and the same blocks, so only float reassociation may differ.  A
# pooling or unsort fault moves pixels by tenths.
FRAME0_MAX_ABS = 1e-3


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (the compile span
    includes persistent-cache retrieval), and persistent-cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.secs = dict.fromkeys(self.EVENTS, 0.0)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_kw):
        if event in self.secs:
            self.secs[event] += duration_secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def total(self) -> float:
        return sum(self.secs.values())


def seeded_params(cfg):
    from repro.core import model as model_lib
    params = model_lib.init_ngp(jax.random.PRNGKey(SEED), cfg)
    return dict(params, grid=params["grid"] * GRID_SCALE)


def make_requests(hw, theta0=THETA0, n=N_FRAMES, dtheta=DTHETA):
    from repro.core import scene
    from repro.serve.render_engine import RenderRequest
    H, W = hw
    return [RenderRequest(rid=i, scene=SCENE,
                          cam=scene.look_at_camera(H, W, theta0 + dtheta * i,
                                                   PHI))
            for i in range(n)]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _reference(cfg, n_samples, params, o, d):
    from repro.core import model as model_lib, pipeline
    R = o.shape[0]
    pad = (-R) % REF_RAYS
    o = jnp.concatenate([o, jnp.zeros((pad, 3))])
    d = jnp.concatenate([d, jnp.tile(jnp.asarray([[0.0, 0.0, 1.0]]),
                                     (pad, 1))])
    fns = model_lib.field_fns(params, cfg)
    rgb = jax.lax.map(
        lambda od: pipeline.render_fixed_fns(fns, od[0], od[1],
                                             n_samples)[0],
        (o.reshape(-1, REF_RAYS, 3), d.reshape(-1, REF_RAYS, 3)))
    return rgb.reshape(-1, 3)[:R]


def reference_pixels(cfg, cam, n_samples, params, stride=REF_STRIDE):
    """Plain float32 fixed-sample render of the pure-jnp model at
    ``highest`` matmul precision, marched in blocks of REF_RAYS rays, of
    the pixels ``[::stride, ::stride]``: (H/stride, W/stride, 3)."""
    from repro.core import scene
    H, W = cam.height, cam.width
    with jax.default_matmul_precision("highest"):
        o, d = (x.reshape(H, W, 3)[::stride, ::stride]
                for x in scene.camera_rays(cam))
        rgb = _reference(cfg, n_samples, params, o.reshape(-1, 3),
                         d.reshape(-1, 3))
    return np.asarray(rgb).reshape(o.shape)


def psnr(a, b) -> float:
    mse = float(np.mean((np.asarray(a, np.float64) - b) ** 2))
    return math.inf if mse == 0 else -10.0 * math.log10(mse)


def check(ok: bool, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def march_kernel_calls(fns, acfg) -> int:
    """How many compiled Pallas calls (``tpu_custom_call``) the engine's
    pooled march lowers to; interpret mode lowers to none."""
    from repro.serve import pool as pool_lib
    march = pool_lib.batched_march(fns, acfg)
    N, B = 1, acfg.block_size
    text = march.func.lower(
        *march.args, jnp.zeros((N, B, 3)), jnp.zeros((N, B, 3)),
        jnp.ones((N,), jnp.int32)).as_text()
    return text.count("tpu_custom_call")


def peak_bytes(dev) -> int:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use", -1)


def serve(engine, reqs, clock):
    c0, t0 = clock.total(), time.perf_counter()
    done = engine.render(reqs)
    wall = time.perf_counter() - t0
    return sorted(done, key=lambda r: r.rid), wall, clock.total() - c0


def print_frames(done):
    for r in done:
        st = r.stats
        print(f"frame {r.rid}: latency_s={r.latency_s:.3f} "
              f"admission_s={st['admission_s']:.3f} "
              f"probe_reused={st['probe_reused']} "
              f"phase2_sample_fraction="
              f"{st['samples_processed'] / st['baseline_samples']:.4f}")


def smoke_one_chip(bundle, hw, clock, on_tpu: bool = True):
    """The one-chip main path and its checks (``on_tpu=False`` rehearses
    it on the CPU at a small size, without the TPU-only checks)."""
    from repro.core import pipeline
    from repro.kernels import ops, resolve_interpret
    from repro.serve import pool as pool_lib
    from repro.serve.render_engine import (RenderServeConfig,
                                           RenderServingEngine)
    cfg, acfg = bundle.model, bundle.asdr
    H, W = hw
    params = seeded_params(cfg)
    mlp_shapes = {k: [tuple(w.shape) for w in v]
                  for k, v in params["mlps"].items()}
    print(f"config: {bundle.name} tables={tuple(params['grid'].shape)} "
          f"mlps={mlp_shapes} frame={H}x{W} ns_full={acfg.ns_full} "
          f"block={acfg.block_size} chunk={acfg.chunk} "
          f"candidates={acfg.candidates}")
    fns = ops.field_fns(params, cfg)
    rcfg = RenderServeConfig()
    print(f"serve config: slots={rcfg.slots} "
          f"blocks_per_batch={rcfg.blocks_per_batch} "
          f"prefetch={rcfg.prefetch} probe_reuse={rcfg.reuse is not None}")

    interpret = resolve_interpret()
    n_calls = march_kernel_calls(fns, acfg)
    print(f"pallas interpret mode: {interpret}")
    print(f"pallas density_call/color_call: "
          f"{'interpreted' if interpret else 'compiled'} "
          f"({n_calls} tpu_custom_call in the pooled march)")
    print("pallas hash_encode_call: not on the path (XLA gather encodes)")
    if on_tpu:
        check(not interpret and n_calls >= 2,
              "fused-MLP kernels compile into the march")

    # warm-up: compile the engine's two large executables, the probe at
    # this frame size and the pooled march batch, outside the timed
    # window (at a far pose and minimum budgets, so it costs little)
    c0, t0 = clock.total(), time.perf_counter()
    far = make_requests(hw, theta0=THETA0 + 1.0, n=1)[0].cam
    jax.block_until_ready(pipeline.probe_phase(fns, acfg, far, None, True,
                                               True))
    z = jnp.zeros((rcfg.blocks_per_batch, acfg.block_size, 3))
    jax.block_until_ready(pool_lib.batched_march(fns, acfg)(
        z, z, jnp.ones((rcfg.blocks_per_batch,), jnp.int32)))
    print(f"warm-up (probe + one march batch): "
          f"wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.total() - c0:.3f} "
          f"persistent_cache_hits={clock.cache_hits}")

    engine = RenderServingEngine({SCENE: fns}, acfg, rcfg)
    check(engine.executor.backend == "sync", "one-chip engine is sync")
    done, wall, serve_compile = serve(engine, make_requests(hw), clock)
    engine.close()
    print(f"served {len(done)} frames: wall_s={wall:.3f} "
          f"compile_s_in_window={serve_compile:.3f}")
    print_frames(done)
    check(len(done) == N_FRAMES, "every request served")
    check(not done[0].stats["probe_reused"], "frame 0 probes fresh")
    check(any(r.stats["probe_reused"] for r in done[1:]),
          "a later frame reuses the probe")

    for r in done:
        check(r.image.shape == (H, W, 3) and np.isfinite(r.image).all(),
              f"frame {r.rid} finite {H}x{W}x3")
        ref = reference_pixels(cfg, r.cam, acfg.ns_full, params)
        p = psnr(r.image[::REF_STRIDE, ::REF_STRIDE], ref)
        print(f"frame {r.rid}: psnr_db_vs_f32_reference={p:.3f} "
              f"(floor {PSNR_FLOOR_DB}; {ref.shape[0]}x{ref.shape[1]} "
              f"pixels, stride {REF_STRIDE})")
        check(p >= PSNR_FLOOR_DB, f"frame {r.rid} PSNR {p:.3f} dB")

    img0, _ = pipeline.render_asdr_image(fns, acfg, done[0].cam)
    diff0 = float(np.max(np.abs(np.asarray(img0) - done[0].image)))
    print(f"frame 0: max_abs_vs_render_asdr_image={diff0:.3e} "
          f"(limit {FRAME0_MAX_ABS})")
    check(diff0 <= FRAME0_MAX_ABS, "frame 0 matches render_asdr_image")
    print(f"compile_s_total={clock.total():.3f} "
          f"(trace+lower+compile) persistent_cache_hits={clock.cache_hits}")
    dev = jax.devices()[0]
    print(f"peak_bytes_in_use={peak_bytes(dev)}")


def smoke_four_chips(bundle, hw, clock, on_tpu: bool = True):
    """Stage A placed on chips 1-3 (DeviceExecutor) while chip 0
    marches, compared frame for frame with the same requests served by
    the synchronous executor on chip 0 alone (``on_tpu=False`` rehearses
    it on forced host devices, without the HBM check)."""
    from repro.kernels import ops
    from repro.serve.render_engine import (RenderServeConfig,
                                           RenderServingEngine)
    cfg, acfg = bundle.model, bundle.asdr
    devs = jax.devices()
    check(len(devs) >= 4, f"four chips (found {len(devs)})")
    params = seeded_params(cfg)
    params_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    fns = ops.field_fns(params, cfg)
    print(f"config: {bundle.name} frame={hw[0]}x{hw[1]} "
          f"params_bytes={params_bytes}")

    # One slot: the other requests queue, so each round speculates Stage A
    # for the next ones (prefetch 2), and the device executor places that
    # work on chips 1-3.  With a slot per request every admission would
    # run inline on chip 0 and the placement would go unexercised.  The
    # poses lie FOUR_CHIP_DTHETA apart, past probe reuse, so every Stage A
    # is a fresh probe of the field: a secondary chip can only run it
    # with the field's params resident there.
    sync = RenderServingEngine({SCENE: fns}, acfg, RenderServeConfig(slots=1))
    spread = RenderServingEngine({SCENE: fns}, acfg,
                                 RenderServeConfig(slots=1, devices=3))
    check(sync.executor.backend == "sync", "chip-0 engine is sync")
    check(spread.executor.backend == "device"
          and len(spread.executor.devices) == 3,
          "Stage A placed on three secondary chips")
    print(f"stage_a_devices: {[str(d) for d in spread.executor.devices]}")
    results = {}
    for name, eng in (("sync", sync), ("device", spread)):
        done, wall, comp = serve(
            eng, make_requests(hw, dtheta=FOUR_CHIP_DTHETA), clock)
        eng.close()
        print(f"{name}: served {len(done)} frames wall_s={wall:.3f} "
              f"compile_s_in_window={comp:.3f}")
        print_frames(done)
        check(len(done) == N_FRAMES, f"{name}: every request served")
        check(not any(r.stats["probe_reused"] for r in done),
              f"{name}: every frame probes fresh")
        results[name] = done
    for a, b in zip(results["sync"], results["device"]):
        check(a.rid == b.rid, "same requests")
        diff = float(np.max(np.abs(a.image - b.image)))
        print(f"frame {a.rid}: device_vs_sync max_abs={diff:.3e} "
              f"bit_identical={bool(np.array_equal(a.image, b.image))}")
        check(np.isfinite(b.image).all(), f"frame {a.rid} finite")
        check(diff <= 1e-5, f"frame {a.rid} matches the chip-0 engine")
    runs = spread.executor.runs
    print(f"stage_a_runs_per_chip: {runs}")
    check(min(runs) > 0, "Stage A ran on every secondary chip")
    peaks = [peak_bytes(d) for d in devs[:4]]
    for d, peak in zip(devs, peaks):
        print(f"peak_bytes_in_use[{d.id}]={peak}")
    if on_tpu:
        check(min(peaks[1:4]) >= params_bytes,
              "every secondary chip held the field params")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip Stage-A placement "
                         "path and its comparison with chip 0 alone")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    from repro.configs.ingp_asdr import CONFIG
    print(f"device_kind: {dev.device_kind} platform={dev.platform} "
          f"count={len(jax.devices())}")
    print(f"jax {jax.__version__}; compile cache {cache}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        smoke_four_chips(CONFIG, FOUR_CHIP_HW, clock)
    else:
        smoke_one_chip(CONFIG, CONFIG.image_hw, clock)
    print(f"total_wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
