"""The two-phase ASDR renderer (paper §5.5 dataflow, TPU-adapted).

Phase I  — probe every d-th pixel at full ``ns``; derive per-pixel sample
           counts (adaptive.py).
Phase II — sort rays into difficulty-homogeneous blocks; march each block
           in a chunked ``lax.while_loop`` running exactly
           ``ceil(block_budget / chunk)`` iterations (+ early termination
           when every ray in the block saturates).  Within a chunk, the
           color MLP runs only on every ``group``-th sample (decouple.py).

The pipeline is written against the ``FieldFns`` protocol (fields.py): the
same code renders the trained Instant-NGP network, the exact analytic
field (tests), or the Pallas MLP kernel path.

Blocks are the data-parallel unit: `render_adaptive` exposes a pure
per-block function that launch/ shards over the ``data`` mesh axis.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from . import adaptive, decouple, fields, rendering, scene
from .fields import FieldFns

LOG_EPS_T = jnp.log(rendering.EARLY_TERM_TRANSMITTANCE)


@dataclasses.dataclass(frozen=True)
class ASDRConfig:
    ns_full: int = 192
    probe_stride: int = 5            # paper's d
    delta: float = 1.0 / 2048.0      # paper's best threshold (Fig. 21a)
    candidates: Tuple[int, ...] = adaptive.DEFAULT_CANDIDATES
    group: int = 2                   # color-decoupling group size n
    block_size: int = 256            # rays per Phase-II block
    chunk: int = 16                  # samples per while_loop iteration
    early_termination: bool = True
    white_background: bool = True
    # Beyond-paper (TPU adaptation): block-level early termination only
    # fires when EVERY ray in a block saturates; sorting Phase-II rays by
    # (count, probe-interpolated opacity) groups saturating rays into the
    # same blocks so whole blocks exit early (EXPERIMENTS.md §Perf).
    sort_by_opacity: bool = False


def render_fixed_fns(
    fns: FieldFns, origins, dirs, n_samples: int, key=None,
    white_background: bool = True,
):
    """Baseline fixed-count renderer over a FieldFns (paper's "original").

    Deliberately NOT jitted here: fns closures may capture model params,
    and a static-fns jit would bake those arrays into the executable and
    recompile per FieldFns construction.  Callers jit at their own
    boundary with the params as arguments (``fields.unbind``), as
    ``probe_phase`` does.
    """
    ts = scene.sample_ts(origins.shape[0], n_samples, key)
    return render_at(fns, origins, dirs, ts, white_background)


def render_at(fns: FieldFns, origins, dirs, ts, white_background=True,
              deltas=None):
    """``render_fixed_fns`` at given sample distances ts (R, S), each
    sample ``deltas`` (R, S) wide (default: ASDR's uniform width)."""
    pts, uniform = scene.points_at(origins, dirs, ts)
    deltas = uniform if deltas is None else deltas
    R, S = pts.shape[:2]
    flat = pts.reshape(-1, 3)
    dflat = jnp.repeat(dirs, S, axis=0)
    sigma, geo = fns.density(flat)
    color = fns.color(geo, dflat)
    sigma = sigma.reshape(R, S)
    color = color.reshape(R, S, 3)
    rgb, acc, weights = rendering.composite(
        sigma, color, deltas, white_background=white_background
    )
    aux = {"sigmas": sigma, "colors": color, "deltas": deltas, "ts": ts,
           "acc": acc, "weights": weights}
    return rgb, aux


def _interval_samples(fns: FieldFns, edges, chunk: int):
    """The samples of a field's own intervals: midpoints and widths
    (B, S') in program distances from spacing-space edges (B, S + 1)
    (``fns.sampler.ts``), padded with zero-width samples to whole
    chunks."""
    t = fns.sampler.ts(edges)
    pad = ((0, 0), (0, -(t.shape[1] - 1) % chunk))
    return (jnp.pad(0.5 * (t[:, 1:] + t[:, :-1]), pad),
            jnp.pad(t[:, 1:] - t[:, :-1], pad))


def _march_block(fns: FieldFns, acfg: ASDRConfig, origins, dirs, budget,
                 density_only: bool = False, edges=None):
    """March one block of rays with a traced per-block sample budget.

    origins/dirs: (B, 3); budget: traced int32 scalar.  With ``edges``
    (B, S + 1), the spacing-space intervals of a field's own sampler,
    sample i of a ray sits at its interval's midpoint and is as wide as
    the interval; else ASDR's ``budget`` uniform midpoints over
    [NEAR, FAR].
    Returns (rgb (B,3), acc (B,), depth (B,), chunks_done scalar,
    ray_chunks (B,) int32) — depth is the per-ray termination depth
    ``E[t] + (1 - acc) * FAR``, the full-resolution replacement for the
    probe's stride-d proxy depth (framecache warps register against it
    at depth edges); ray_chunks counts the chunks each ray entered
    still live (un-saturated), the per-RAY refinement of chunks_done.

    With ``density_only`` (static) the color MLP never runs and rgb stays
    zero — the march only produces acc/depth, for rays whose radiance is
    served from the warp/radiance tiers (serve/README.md).
    """
    B = origins.shape[0]
    C = acfg.chunk
    if edges is None:
        delta_t = (scene.FAR - scene.NEAR) / budget.astype(jnp.float32)
    else:
        mids, widths = _interval_samples(fns, edges, C)
    n_chunks = (budget + C - 1) // C

    def cond(state):
        ci, log_t = state[0], state[1]
        alive = jnp.any(log_t > LOG_EPS_T) if acfg.early_termination else True
        return jnp.logical_and(ci < n_chunks, alive)

    def body(state):
        ci, log_t, rgb, acc, dep, ray_chunks = state
        # per-ray liveness at chunk start: saturated rays stop counting
        # toward ray_chunks
        alive = log_t > LOG_EPS_T
        idx = ci * C + jnp.arange(C)
        valid = idx < budget
        if edges is None:
            ts = scene.NEAR + (idx.astype(jnp.float32) + 0.5) * delta_t
            pts = origins[:, None, :] + ts[None, :, None] * dirs[:, None, :]
            dt = delta_t
        else:
            ts = jax.lax.dynamic_slice_in_dim(mids, ci * C, C, axis=1)
            dt = jax.lax.dynamic_slice_in_dim(widths, ci * C, C, axis=1)
            pts = origins[:, None, :] + ts[..., None] * dirs[:, None, :]
        flat = pts.reshape(-1, 3)
        sigma, geo = fns.density(flat)
        sigma = sigma.reshape(B, C)
        sigma = jnp.where(valid[None, :], sigma, 0.0)

        if not density_only:
            geo = geo.reshape(B, C, -1)
            # color-density decoupling within the chunk
            a_idx = jnp.arange(0, C, acfg.group)
            A = a_idx.shape[0]
            geo_a = geo[:, a_idx].reshape(B * A, -1)
            dirs_a = jnp.repeat(dirs, A, axis=0)
            col_a = fns.color(geo_a, dirs_a).reshape(B, A, 3)
            colors = decouple.interpolate_group_colors(col_a, acfg.group, C)

        with jax.named_scope("composite"):
            alphas = rendering.alphas_from_sigmas(sigma, dt)
            one_m = jnp.clip(1.0 - alphas, 1e-10, 1.0)
            log_steps = jnp.log(one_m)
            # transmittance inside the chunk, carried from previous chunks
            intra = jnp.cumsum(log_steps, axis=-1) - log_steps  # exclusive
            trans = jnp.exp(log_t[:, None] + intra)
            w = trans * alphas
            if not density_only:
                rgb = rgb + jnp.sum(w[..., None] * colors, axis=1)
            acc = acc + jnp.sum(w, axis=-1)
            dep = dep + jnp.sum(w * (ts if edges is not None else ts[None, :]),
                                axis=-1)
            log_t = log_t + jnp.sum(log_steps, axis=-1)
        ray_chunks = ray_chunks + alive.astype(jnp.int32)
        return ci + 1, log_t, rgb, acc, dep, ray_chunks

    state = (
        jnp.asarray(0, jnp.int32),
        jnp.zeros((B,)),
        jnp.zeros((B, 3)),
        jnp.zeros((B,)),
        jnp.zeros((B,)),
        jnp.zeros((B,), jnp.int32),
    )
    ci, _, rgb, acc, dep, ray_chunks = jax.lax.while_loop(cond, body, state)
    # an early-terminated ray leaves a negligible transmittance tail; the
    # (1 - acc) * FAR term pins true background rays to the far plane
    depth = dep + (1.0 - acc) * scene.FAR
    if acfg.white_background and not density_only:
        rgb = rgb + (1.0 - acc[:, None])
    return rgb, acc, depth, ci, ray_chunks


def march_blocks(fns: FieldFns, acfg: ASDRConfig, o_b, d_b, budgets,
                 density_only: bool = False, edges=None):
    """March a batch of blocks: o_b/d_b (N, B, 3), budgets (N,) int32 ->
    (rgb (N,B,3), acc (N,B), depth (N,B), chunks (N,), ray_chunks
    (N,B) int32).  ``edges`` (N, B, S + 1): the rays' intervals, for a
    field that brings its own sampler.

    Phase II: each block runs the chunked march above under ``lax.map``.
    """
    march = partial(_march_block, fns, acfg, density_only=density_only)
    if edges is not None:
        return jax.lax.map(lambda args: march(*args[:3], edges=args[3]),
                           (o_b, d_b, budgets, edges))
    return jax.lax.map(lambda args: march(*args), (o_b, d_b, budgets))


def block_sort(acfg: ASDRConfig, counts, opacity=None):
    """Sort rays into difficulty-homogeneous blocks: (order, budgets).

    Shared by render_adaptive and the render serving engine so that pooled
    serving blocks are built with exactly the single-image semantics.
    counts: (R,) int32 with R % block_size == 0.
    """
    R = counts.shape[0]
    B = acfg.block_size
    if acfg.sort_by_opacity and opacity is not None:
        # composite key: count (primary), quantized opacity (secondary)
        key = counts.astype(jnp.int32) * 1024 + jnp.clip(
            (opacity * 1023).astype(jnp.int32), 0, 1023)
        order = jnp.argsort(key).astype(jnp.int32)
        sorted_counts = counts[order]
        budgets = sorted_counts.reshape(R // B, B).max(axis=1)
        return order, budgets
    return adaptive.sort_rays_into_blocks(counts, B)


def pad_rays_to_blocks(acfg: ASDRConfig, origins, dirs, counts, opacity=None):
    """Pad rays to a block_size multiple with minimum-count dummy rays.

    Pad rays point +z from the origin corner, get the cheapest budget, and
    never reach the image: callers crop to the first R rows after unsort.
    Returns (origins, dirs, counts, opacity, pad).
    """
    R = origins.shape[0]
    pad = (-R) % acfg.block_size
    if pad:
        origins = jnp.concatenate([origins, jnp.zeros((pad, 3))], axis=0)
        dirs = jnp.concatenate(
            [dirs, jnp.tile(jnp.asarray([[0.0, 0.0, 1.0]]), (pad, 1))], axis=0
        )
        counts = jnp.concatenate(
            [counts, jnp.full((pad,), min(acfg.candidates), jnp.int32)],
            axis=0,
        )
        if opacity is not None:
            opacity = jnp.concatenate([opacity, jnp.zeros((pad,))], axis=0)
    return origins, dirs, counts, opacity, pad


def render_adaptive(fns: FieldFns, acfg: ASDRConfig, origins, dirs, counts,
                    opacity=None):
    """Phase II: sorted-block adaptive render.

    origins/dirs: (R, 3) with R % block_size == 0; counts: (R,) int32;
    opacity: optional (R,) probe-interpolated accumulated opacity used as a
    secondary sort key (see ASDRConfig.sort_by_opacity).
    Returns (rgb (R,3), acc (R,), stats).
    """
    R = origins.shape[0]
    B = acfg.block_size
    order, budgets = block_sort(acfg, counts, opacity)
    o_s = origins[order].reshape(-1, B, 3)
    d_s = dirs[order].reshape(-1, B, 3)

    rgb_s, acc_s, depth_s, chunks, ray_chunks = march_blocks(
        fns, acfg, o_s, d_s, budgets)
    # unsort
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(R, dtype=order.dtype))
    rgb = rgb_s.reshape(R, 3)[inv]
    acc = acc_s.reshape(R)[inv]
    stats = {
        "samples_processed": jnp.sum(chunks) * B * acfg.chunk,
        "baseline_samples": R * acfg.ns_full,
        "chunks_per_block": chunks,
        # per-ray live-chunk counts (block-sorted order): the gap to
        # chunks_per_block is the chunks a ray rode after it saturated
        "ray_chunks_per_block": ray_chunks,
        "budgets": budgets,
        # full-resolution termination depth (ROADMAP item): replaces the
        # probe's stride-d proxy depth wherever a finished frame is cached
        "term_depth": depth_s.reshape(R)[inv],
    }
    return rgb, acc, stats


def probe_rays(fns: FieldFns, acfg: ASDRConfig, origins, dirs, ts):
    """Phase-I march of the probe rays -> per-ray (counts (R,) int32,
    acc (R,), expected termination depth (R,)).

    Marches fixed blocks under ``lax.map``, each holding as many samples
    as one Phase-II chunk step (``block_size * chunk``), so the probe's
    per-sample temporaries are bounded like the march's instead of
    scaling with the frame (one 800x800 probe at 192 samples is 4.9M
    samples, ~16 GB of temporaries on a TPU).  Every output is per ray,
    so blocking changes no value; pad rays are cropped.
    """
    R, S = ts.shape
    B = max(1, acfg.block_size * acfg.chunk // S)
    pad = (-R) % B
    if pad:
        origins = jnp.concatenate([origins, jnp.zeros((pad, 3))], axis=0)
        dirs = jnp.concatenate(
            [dirs, jnp.tile(jnp.asarray([[0.0, 0.0, 1.0]]), (pad, 1))],
            axis=0)
        ts = jnp.concatenate([ts, jnp.broadcast_to(ts[:1], (pad, S))],
                             axis=0)

    def block(args):
        rgb, aux = render_at(fns, *args,
                             white_background=acfg.white_background)
        counts = adaptive.probe_counts(
            aux["sigmas"], aux["colors"], rgb, acfg.ns_full,
            acfg.candidates, acfg.delta)
        t_exp = rendering.expected_termination_depth(
            aux["weights"], aux["ts"], aux["acc"], scene.FAR)
        return counts, aux["acc"], t_exp

    out = jax.lax.map(block, (origins.reshape(-1, B, 3),
                              dirs.reshape(-1, B, 3),
                              ts.reshape(-1, B, S)))
    return tuple(x.reshape(-1)[:R] for x in out)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _probe_maps(fkey, acfg: ASDRConfig, geom, params, rot, origin,
                probe_key):
    """The whole probe, jitted with the field params as arguments:
    (counts, opacity, depth), each (H*W,)."""
    H, W, focal = geom
    o, d = scene.camera_rays(scene.Camera(H, W, focal, rot, origin))
    jj, ii = jnp.meshgrid(jnp.arange(0, H, acfg.probe_stride),
                          jnp.arange(0, W, acfg.probe_stride),
                          indexing="ij")
    probe_idx = (jj * W + ii).reshape(-1)
    probe_hw = (jj.shape[0], jj.shape[1])
    ts = scene.sample_ts(probe_idx.shape[0], acfg.ns_full, probe_key)
    pcounts, acc, t_exp = probe_rays(fields.rebind(fkey, params), acfg,
                                     o[probe_idx], d[probe_idx], ts)
    counts = adaptive.interpolate_counts(
        pcounts, probe_hw, (H, W), acfg.candidates, acfg.ns_full)
    opacity = adaptive.interpolate_map(acc, probe_hw, (H, W))
    depth = adaptive.interpolate_map(t_exp, probe_hw, (H, W))
    return counts, opacity, depth


def probe_phase(fns: FieldFns, acfg: ASDRConfig, cam, probe_key=None,
                return_opacity: bool = False, return_depth: bool = False):
    """Phase I: strided probe -> per-pixel sample-count map (H*W,).

    With return_opacity, also bilinearly interpolates the probe rays'
    accumulated opacity over the image (secondary block-sort key).  With
    return_depth, additionally interpolates each probe ray's expected
    termination distance (background pinned to FAR) — the proxy depth the
    framecache warp primitive reprojects per-pixel maps with.

    One jitted call (``_probe_maps``) with the field params passed as
    arguments; the probe rays march in blocks (``probe_rays``)."""
    fkey, params = fields.unbind(fns)
    counts, opacity, depth = _probe_maps(
        fkey, acfg, (cam.height, cam.width, cam.focal), params,
        jnp.asarray(cam.c2w_rot), jnp.asarray(cam.origin), probe_key)
    stride = acfg.probe_stride
    n_probe = (-(-cam.height // stride)) * (-(-cam.width // stride))
    probe_cost = n_probe * acfg.ns_full
    if not (return_opacity or return_depth):
        return counts, probe_cost
    if not return_depth:
        return counts, probe_cost, opacity
    return counts, probe_cost, opacity, depth


@partial(jax.jit, static_argnums=(0, 1, 2))
def proposal_edges(fkey, block: int, geom, params, rot, origin):
    """Stage A of a field that brings its own sampler, jitted with the
    field params as arguments: every pixel ray's interval edges (H*W,
    S + 1) from ``sampler.edges``, in blocks of ``block`` rays under
    ``lax.map`` (pad rays cropped)."""
    H, W, focal = geom
    o, d = scene.camera_rays(scene.Camera(H, W, focal, rot, origin))
    R = H * W
    pad = (-R) % block
    o = jnp.concatenate([o, jnp.zeros((pad, 3))], axis=0)
    d = jnp.concatenate([d, jnp.tile(jnp.asarray([[0.0, 0.0, 1.0]]), (pad, 1))],
                        axis=0)
    sampler = fields.rebind(fkey, params).sampler
    e = jax.lax.map(lambda od: sampler.edges(*od),
                    (o.reshape(-1, block, 3), d.reshape(-1, block, 3)))
    return e.reshape(R + pad, -1)[:R]


def proposal_phase(fns: FieldFns, acfg: ASDRConfig, cam):
    """``proposal_edges`` for one camera, ``acfg.block_size`` rays a
    block: (H*W, S + 1) spacing-space edges."""
    fkey, params = fields.unbind(fns)
    block = min(acfg.block_size, cam.height * cam.width)
    return proposal_edges(fkey, block, (cam.height, cam.width, cam.focal),
                          params, jnp.asarray(cam.c2w_rot),
                          jnp.asarray(cam.origin))


def render_asdr_image(fns: FieldFns, acfg: ASDRConfig, cam, probe_key=None):
    """Full two-phase ASDR render of a camera view.

    Returns (image (H,W,3), stats dict).
    """
    H, W = cam.height, cam.width
    o, d = scene.camera_rays(cam)

    opacity = None
    if acfg.sort_by_opacity:
        counts, probe_cost, opacity = probe_phase(
            fns, acfg, cam, probe_key, return_opacity=True)
    else:
        counts, probe_cost = probe_phase(fns, acfg, cam, probe_key)

    # ---- Phase II ----
    R = H * W
    o, d, counts, opacity, _pad = pad_rays_to_blocks(
        acfg, o, d, counts, opacity)
    rgb, acc, stats = render_adaptive(fns, acfg, o, d, counts, opacity)
    img = rgb[:R].reshape(H, W, 3)

    stats = dict(stats)
    stats.update(adaptive.compute_savings(counts[:R], acfg.ns_full))
    stats["probe_samples"] = probe_cost
    stats["phase2_fraction_of_baseline"] = (
        stats["samples_processed"] / stats["baseline_samples"]
    )
    return img, stats


# --------------------------------------------------------------------------
# DEPRECATED import path: cross-frame reuse moved to repro.framecache.
# ``ProbeCache`` / ``ProbeReuseConfig`` / ``probe_phase_cached`` now live in
# framecache/probe.py (rebuilt on the pose-delta warp primitive); the lazy
# module __getattr__ below keeps `from repro.core.pipeline import ProbeCache`
# working without a core -> framecache import cycle at module load.
# --------------------------------------------------------------------------
_FRAMECACHE_REEXPORTS = ("ProbeCache", "ProbeReuseConfig",
                         "probe_phase_cached")


def __getattr__(name):  # PEP 562 — lazy deprecation re-exports
    if name in _FRAMECACHE_REEXPORTS:
        from ..framecache import probe as _probe
        return getattr(_probe, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


