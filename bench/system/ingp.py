"""The system under test for an Instant-NGP configuration: the program's
``RenderServingEngine`` over its kernel-backed field (``kernels.ops``),
built from the configuration file's sizes.  Nothing here decides what is
correct or how fast it is; it only hands the program its inputs."""
from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashgrid, mlp, scene
from repro.core.model import NGPConfig
from repro.core.pipeline import ASDRConfig
from repro.framecache.probe import ProbeReuseConfig
from repro.kernels import ops
from repro.serve.render_engine import (RenderRequest, RenderServeConfig,
                                       RenderServingEngine)

SCENE = "field"


class TimedRequest(RenderRequest):
    """A request that stamps the host clock when the engine hands it its
    frame (``finalize`` assigns ``stats`` last)."""

    def __setattr__(self, name, value):
        object.__setattr__(self, name, value)
        if name == "stats" and value:
            object.__setattr__(self, "done_at", time.perf_counter())


@dataclasses.dataclass
class System:
    # the march executable's name in a device trace: serve/pool.py jits
    # a functools.partial of ``_march``, which JAX names ``_unknown``
    MARCH_MODULE = "jit__unknown"

    cfg: dict
    ngp: NGPConfig
    acfg: ASDRConfig
    rcfg: RenderServeConfig

    def march_module(self, ctx) -> dict | None:
        """The pooled march's entry in the window's trace (``seconds``,
        ``launches``), or None with the reason on stderr.  The name is
        JAX's for any jitted ``functools.partial`` (the density-only march
        shares it), so the entry counts only where its launches equal the
        engine's batches and no frame pooled density-only rays."""
        tr = ctx["trace"]
        if not tr:
            return None
        m = tr["modules"].get(self.MARCH_MODULE)
        launches = m["launches"] if m else 0
        batches = ctx["engine_stats"]["batches"]
        density = sum(r.stats.get("density_rays", 0) for r in ctx["served"])
        if launches != batches or not batches or density:
            print(f"march module {self.MARCH_MODULE}: {launches} launches in the trace, "
                  f"{batches} batches in the engine, {density} density-only rays; "
                  f"no march time read", file=sys.stderr, flush=True)
            return None
        return m

    def engine(self, params) -> RenderServingEngine:
        return RenderServingEngine({SCENE: ops.field_fns(params, self.ngp)},
                                   self.acfg, self.rcfg)

    def request(self, rid: int, cam, arrival_s: float) -> TimedRequest:
        H, W, focal, rot, origin = cam
        c = scene.Camera(H, W, float(focal), np.asarray(rot, np.float32),
                         np.asarray(origin, np.float32))
        req = TimedRequest(rid=rid, scene=SCENE, cam=c, arrival_s=arrival_s)
        object.__setattr__(req, "done_at", None)
        return req

    def serve(self, engine, requests) -> float:
        """Serve every request; returns the host time of the call."""
        t0 = time.perf_counter()
        engine.render(requests)
        return t0

    def warm_up(self, params, cams):
        """Run the engine once over ``cams`` (frames of the cell's size
        and kind) with all-zero weights of the served shapes: every
        executable and eager op the window uses compiles here."""
        zeros = jax.tree.map(jnp.zeros_like, params)
        eng = self.engine(zeros)
        reqs = [self.request(i, c, 0.0) for i, c in enumerate(cams)]
        eng.render(reqs)
        eng.close()
        jax.block_until_ready(zeros)

    def time_chunk_step(self, params) -> float:
        """Seconds of one chunk step of one block (the pooled march at the
        minimum budget, timed on its second call)."""
        from repro.serve import pool
        march = pool.batched_march(ops.field_fns(params, self.ngp), self.acfg)
        n, B = self.rcfg.blocks_per_batch, self.acfg.block_size
        z = jnp.zeros((n, B, 3))
        budgets = jnp.ones((n,), jnp.int32)
        jax.block_until_ready(march(z, z, budgets))
        t0 = time.perf_counter()
        jax.block_until_ready(march(z, z, budgets))
        return (time.perf_counter() - t0) / n

    def time_probe(self, params, cam) -> float:
        from repro.core import pipeline
        fns = ops.field_fns(params, self.ngp)
        c = self.request(0, cam, 0.0).cam
        jax.block_until_ready(pipeline.probe_phase(fns, self.acfg, c, None, True, True))
        t0 = time.perf_counter()
        jax.block_until_ready(pipeline.probe_phase(fns, self.acfg, c, None, True, True))
        return time.perf_counter() - t0


def build(cfg: dict) -> System:
    g, m, a, s = cfg["grid"], cfg["mlp"], cfg["asdr"], cfg["serve"]
    if (cfg["scene"]["near"], cfg["scene"]["far"]) != (scene.NEAR, scene.FAR):
        raise SystemExit(f"the program's ray bounds {(scene.NEAR, scene.FAR)} "
                         f"differ from the configuration's {cfg['scene']}")
    if cfg["dtype"] != "float32":
        raise SystemExit(f"the program serves float32 fields, not {cfg['dtype']}")
    grid = hashgrid.HashGridConfig(**g)
    net = mlp.MLPConfig(encoding_dim=grid.output_dim, **m)
    ngp = NGPConfig(grid=grid, net=net)
    acfg = ASDRConfig(ns_full=a["ns_full"], probe_stride=a["probe_stride"],
                      delta=a["delta"], candidates=tuple(a["candidates"]),
                      group=a["group"], block_size=a["block_size"],
                      chunk=a["chunk"], early_termination=a["early_termination"],
                      white_background=a["white_background"])
    reuse = s.get("probe_reuse")
    rcfg = RenderServeConfig(slots=s["slots"], blocks_per_batch=s["blocks_per_batch"],
                             prefetch=s["prefetch"],
                             reuse=ProbeReuseConfig(**reuse) if reuse else None)
    return System(cfg, ngp, acfg, rcfg)
