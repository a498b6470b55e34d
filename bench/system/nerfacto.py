"""The system under test for a nerfacto configuration: the program's
``RenderServingEngine`` over its nerfacto field (``core.nerfacto``), built
from the configuration file's sizes.  Stage A runs the field's proposal
sampler (``jit_proposal_edges``) in place of ASDR's probe, and the march
takes its intervals.  The engine, requests and the march's trace entry
are the Instant-NGP adapter's."""
from __future__ import annotations

import dataclasses
import math
import sys
import time

import jax
import jax.numpy as jnp

from bench.system import ingp
from repro.core import hashgrid, mlp, nerfacto, pipeline, scene
from repro.core.pipeline import ASDRConfig
from repro.serve.render_engine import RenderServeConfig, RenderServingEngine


@dataclasses.dataclass
class System(ingp.System):
    # Stage A's executable: ``pipeline.proposal_edges``
    PROPOSAL_MODULE = "jit_proposal_edges"

    model: nerfacto.NerfactoConfig = None
    _field: tuple = None

    def fields(self, params):
        """The field over ``params``, built once for them: a build derives
        ~4 GB of corner rows on a v5e."""
        if self._field is None or self._field[0] is not params:
            self._field = None
            self._field = (params, nerfacto.field_fns(params, self.model))
        return self._field[1]

    def engine(self, params) -> RenderServingEngine:
        return RenderServingEngine({ingp.SCENE: self.fields(params)}, self.acfg, self.rcfg)

    def proposal_module(self, ctx) -> dict | None:
        """The proposal executable's entry in the window's trace, or None
        (reason on stderr) unless it launched once a frame that ran it."""
        tr = ctx["trace"]
        if not tr:
            return None
        m = tr["modules"].get(self.PROPOSAL_MODULE)
        launches = m["launches"] if m else 0
        frames = sum(1 for r in ctx["served"] if r.stats.get("probe_samples"))
        if launches != frames or not frames:
            print(f"proposal module {self.PROPOSAL_MODULE}: {launches} launches in the "
                  f"trace, {frames} frames prepared; no proposal time read",
                  file=sys.stderr, flush=True)
            return None
        return m

    def time_chunk_step(self, params) -> float:
        """Seconds of one chunk step of one block, as a batch spends it:
        one pooled march of ``blocks_per_batch`` look-at views of a block
        each at the intervals the proposal sampler gives them, over the
        batch's chunk steps, timed on its second call."""
        from repro.serve import pool
        fns = self.fields(params)
        march = pool.batched_march(fns, self.acfg)
        n, B, a = self.rcfg.blocks_per_batch, self.acfg.block_size, self.acfg
        side = math.isqrt(B)
        cams = [scene.look_at_camera(side, B // side, 2 * math.pi * k / n, 0.5)
                for k in range(n)]
        o, d = (jnp.stack([scene.camera_rays(c)[i] for c in cams]) for i in (0, 1))
        edges = jnp.stack([pipeline.proposal_phase(fns, a, c) for c in cams])
        budgets = jnp.full((n,), a.ns_full, jnp.int32)
        jax.block_until_ready(march(o, d, budgets, edges))
        t0 = time.perf_counter()
        jax.block_until_ready(march(o, d, budgets, edges))
        return (time.perf_counter() - t0) / (n * -(-a.ns_full // a.chunk))

    def time_probe(self, params, cam) -> float:
        """Seconds of Stage A's proposal executable for one frame."""
        fns = self.fields(params)
        c = self.request(0, cam, 0.0).cam
        jax.block_until_ready(pipeline.proposal_phase(fns, self.acfg, c))
        t0 = time.perf_counter()
        jax.block_until_ready(pipeline.proposal_phase(fns, self.acfg, c))
        return time.perf_counter() - t0


def _grid(g: dict) -> hashgrid.HashGridConfig:
    return hashgrid.HashGridConfig(**{k: g[k] for k in (
        "n_levels", "log2_table_size", "feature_dim", "base_resolution", "max_resolution")})


def build(cfg: dict) -> System:
    m, sc, sp, a, s = cfg["mlp"], cfg["scene"], cfg["sampler"], cfg["asdr"], cfg["serve"]
    fixed = {"near_plane": nerfacto.NEAR, "far_plane": nerfacto.FAR,
             "world_scale": nerfacto.WORLD_SCALE, "world_center": list(nerfacto.CENTER),
             "histogram_padding": nerfacto.HISTOGRAM_PADDING, "pdf_eps": nerfacto.PDF_EPS}
    if {k: {**sc, **sp}[k] for k in fixed} != fixed:
        raise SystemExit(f"the program's nerfacto constants {fixed} differ from the "
                         f"configuration's {sc} {sp}")
    if cfg["dtype"] != "float32":
        raise SystemExit(f"the program serves float32 fields, not {cfg['dtype']}")
    props = cfg["proposal_nets"]
    if {p["num_layers"] for p in props} != {2}:
        raise SystemExit(f"the program's proposal MLPs have one hidden layer: {props}")
    samples = tuple(sp["num_proposal_samples_per_ray"]) + (sp["num_nerf_samples_per_ray"],)
    if (a["ns_full"], tuple(a["candidates"]), a["group"]) != (samples[-1], samples[-1:], 1):
        raise SystemExit(f"the march takes all {samples[-1]} samples with their color: {a}")
    grid = _grid(cfg["grid"])
    model = nerfacto.NerfactoConfig(
        grid=grid,
        net=mlp.MLPConfig(encoding_dim=grid.output_dim, **{k: m[k] for k in (
            "density_hidden", "density_layers", "geo_feature_dim", "sh_degree",
            "color_hidden", "color_layers")}),
        proposal_grids=tuple(_grid(p) for p in props), samples=samples)
    acfg = ASDRConfig(ns_full=a["ns_full"], candidates=tuple(a["candidates"]),
                      group=a["group"], block_size=a["block_size"], chunk=a["chunk"],
                      early_termination=a["early_termination"],
                      white_background=a["white_background"])
    rcfg = RenderServeConfig(slots=s["slots"], blocks_per_batch=s["blocks_per_batch"],
                             prefetch=s["prefetch"], reuse=None)
    return System(cfg, None, acfg, rcfg, model)
