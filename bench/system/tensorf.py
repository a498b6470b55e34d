"""The system under test for a TensoRF configuration: the program's
``RenderServingEngine`` over its VM field (``core.tensorf``), built from
the configuration file's sizes.  The engine, requests and the march's
trace entry are the Instant-NGP adapter's; only the field differs."""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp

from bench.system import ingp
from repro.core import pipeline, scene, tensorf
from repro.core.pipeline import ASDRConfig
from repro.framecache.probe import ProbeReuseConfig
from repro.serve.render_engine import RenderServeConfig, RenderServingEngine


@dataclasses.dataclass
class System(ingp.System):
    model: tensorf.TensoRFConfig = None

    def fields(self, params):
        return tensorf.field_fns(params, self.model)

    def engine(self, params) -> RenderServingEngine:
        return RenderServingEngine({ingp.SCENE: self.fields(params)}, self.acfg, self.rcfg)

    def time_chunk_step(self, params) -> float:
        """Seconds of one chunk step of one block, as a full-budget batch
        spends it: one pooled march of ``blocks_per_batch`` look-at views
        of a block each, through the box at ``ns_full`` samples, over the
        batch's chunk steps.  The tables are zeros: a gather costs the
        same whatever it reads, and no ray saturates, so no block ends
        early.  A one-chunk march alone carries each block's fixed cost
        into its one step (~42 against ~27 ms a step on one v5e) and would
        size a closed batch a third short of ``--seconds``."""
        from repro.serve import pool
        zeros = jax.tree.map(jnp.zeros_like, params)
        march = pool.batched_march(self.fields(zeros), self.acfg)
        n, B, a = self.rcfg.blocks_per_batch, self.acfg.block_size, self.acfg
        side = math.isqrt(B)
        rays = [scene.camera_rays(scene.look_at_camera(side, B // side, 2 * math.pi * k / n, 0.5))
                for k in range(n)]
        o, d = (jnp.stack([r[i] for r in rays]) for i in (0, 1))
        jax.block_until_ready(march(o, d, jnp.ones((n,), jnp.int32)))
        t0 = time.perf_counter()
        jax.block_until_ready(march(o, d, jnp.full((n,), a.ns_full, jnp.int32)))
        return (time.perf_counter() - t0) / (n * -(-a.ns_full // a.chunk))

    def time_probe(self, params, cam) -> float:
        fns = self.fields(params)
        c = self.request(0, cam, 0.0).cam
        jax.block_until_ready(pipeline.probe_phase(fns, self.acfg, c, None, True, True))
        t0 = time.perf_counter()
        jax.block_until_ready(pipeline.probe_phase(fns, self.acfg, c, None, True, True))
        return time.perf_counter() - t0


def build(cfg: dict) -> System:
    t, a, s = cfg["tensorf"], cfg["asdr"], cfg["serve"]
    if (cfg["scene"]["near"], cfg["scene"]["far"]) != (scene.NEAR, scene.FAR):
        raise SystemExit(f"the program's ray bounds {(scene.NEAR, scene.FAR)} "
                         f"differ from the configuration's {cfg['scene']}")
    if cfg["dtype"] != "float32":
        raise SystemExit(f"the program serves float32 fields, not {cfg['dtype']}")
    lo, hi = t["aabb"]
    fixed = {"fea_pe": tensorf.FEATURE_PE, "view_pe": tensorf.VIEW_PE,
             "density_shift": tensorf.DENSITY_SHIFT,
             "distance_scale": tensorf.DISTANCE_SCALE}
    if {k: t[k] for k in fixed} != fixed or hi - lo != tensorf.BOX_SIZE:
        raise SystemExit(f"the program's TensoRF constants {fixed}, box {tensorf.BOX_SIZE}, "
                         f"differ from the configuration's {t}")
    model = tensorf.TensoRFConfig(
        grid=t["grid"], density_components=t["n_lamb_sigma"],
        appearance_components=t["n_lamb_sh"], feature_dim=t["data_dim_color"],
        hidden=t["featureC"])
    acfg = ASDRConfig(ns_full=a["ns_full"], probe_stride=a["probe_stride"],
                      delta=a["delta"], candidates=tuple(a["candidates"]),
                      group=a["group"], block_size=a["block_size"],
                      chunk=a["chunk"], early_termination=a["early_termination"],
                      white_background=a["white_background"])
    reuse = s.get("probe_reuse")
    rcfg = RenderServeConfig(slots=s["slots"], blocks_per_batch=s["blocks_per_batch"],
                             prefetch=s["prefetch"],
                             reuse=ProbeReuseConfig(**reuse) if reuse else None)
    return System(cfg, None, acfg, rcfg, model)
