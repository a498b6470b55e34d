"""Depth-guided reprojection of per-pixel maps between nearby camera poses.

The cross-frame reuse primitive (Cicero-style, arXiv 2404.11852): a map
computed per-pixel at pose A — Phase-I sample counts, probe opacity, or a
finished Phase-II radiance image — is *forward-warped* to a nearby pose B
by lifting every source pixel to a world point with its proxy depth
(the probe's expected termination distance), projecting that point into
B's image, and splatting the map value at the landing pixel.

Two reductions cover the two map kinds:

  * ``scatter_max`` — conservative max over all source pixels landing on a
    target pixel; used for sample-count maps, where over-sampling is safe
    and under-sampling is not.
  * ``nearest_source`` — z-buffered winner (smallest distance in the target
    frame, ties to the lowest source index, so the result is deterministic
    under XLA's unordered scatter); used for radiance/opacity/depth, where
    the nearest surface is the correct value.

Target pixels no source pixel lands on are *disocclusions* (content the
cached pose never saw — revealed by translation, or entering from
off-screen) and come back with ``valid=False``: callers must fill them
conservatively (counts -> ns_full) or march them fresh (radiance).

Everything here is jnp on flat (H*W,) maps — warps run on device, one
scatter/gather per reused frame, no Python per-pixel work.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..core import scene
from ..obs import trace as trace_lib


def project_to_camera(points: jnp.ndarray, cam) -> Tuple[jnp.ndarray,
                                                         jnp.ndarray,
                                                         jnp.ndarray]:
    """Project world points into a camera's pixel grid.

    points: (N, 3).  Returns (flat pixel index (N,), ok (N,) bool,
    distance (N,)): ``ok`` is False for points behind the camera or
    landing outside the image; ``distance`` is the euclidean eye distance
    (the depth a ray from ``cam`` through that pixel would record).
    """
    H, W = cam.height, cam.width
    # full float32, as in scene.camera_rays: at bfloat16 operands (a TPU's
    # default) an 800-pixel frame's projection lands pixels off by ~2
    rel = jnp.matmul(points - jnp.asarray(cam.origin),
                     jnp.asarray(cam.c2w_rot),
                     precision=jax.lax.Precision.HIGHEST)
    z = rel[:, 2]
    in_front = z > 1e-6
    zs = jnp.where(in_front, z, 1.0)
    # inverse of scene.camera_rays' pixel -> direction mapping
    i = jnp.round(rel[:, 0] / zs * cam.focal + 0.5 * W - 0.5).astype(jnp.int32)
    j = jnp.round(-rel[:, 1] / zs * cam.focal + 0.5 * H - 0.5).astype(jnp.int32)
    ok = in_front & (i >= 0) & (i < W) & (j >= 0) & (j < H)
    dist = jnp.linalg.norm(points - jnp.asarray(cam.origin), axis=-1)
    return j * W + i, ok, dist


def forward_warp(cam_src, cam_dst, depth_src: jnp.ndarray):
    """Reproject every source pixel into the destination image.

    depth_src: (H*W,) distance along each source ray (unit directions, so
    world point = origin + depth * dir).  Returns (target flat index,
    ok mask, distance in the destination frame), each (H*W,).
    """
    o, d = scene.camera_rays(cam_src)
    pts = o + depth_src[:, None] * d
    return project_to_camera(pts, cam_dst)


def scatter_max(values: jnp.ndarray, tgt_idx: jnp.ndarray, ok: jnp.ndarray,
                n_pixels: int, fill) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Max-splat ``values`` onto an ``n_pixels`` map.

    Returns (warped (n_pixels,), valid (n_pixels,) bool); pixels nothing
    landed on hold ``fill`` and valid=False.  Max over contributors is the
    conservative reduction for count maps: when several source pixels
    collapse onto one target pixel (occlusion fold-over), the target gets
    the most demanding count among them.
    """
    idx = jnp.where(ok, tgt_idx, n_pixels)        # off-image spill bin
    out = jnp.full((n_pixels + 1,), fill, values.dtype).at[idx].max(values)
    hit = jnp.zeros((n_pixels + 1,), jnp.int32).at[idx].add(1)
    return out[:n_pixels], hit[:n_pixels] > 0


def nearest_source(tgt_idx: jnp.ndarray, ok: jnp.ndarray, dist: jnp.ndarray,
                   n_pixels: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Z-buffered winning source pixel per target pixel.

    Returns (src (n_pixels,) int32 — index into the source map, clamped to
    0 where invalid — and valid (n_pixels,) bool).  The winner is the
    contributor with the smallest destination-frame distance; among
    near-ties (within a relative epsilon, e.g. coplanar splats) the lowest
    source index wins, making the scatter deterministic.
    """
    N = tgt_idx.shape[0]
    idx = jnp.where(ok, tgt_idx, n_pixels)
    best = jnp.full((n_pixels + 1,), jnp.inf).at[idx].min(
        jnp.where(ok, dist, jnp.inf))
    is_best = ok & (dist <= best[idx] * (1.0 + 1e-5) + 1e-6)
    cand = jnp.where(is_best, idx, n_pixels)
    win = jnp.full((n_pixels + 1,), N, jnp.int32).at[cand].min(
        jnp.arange(N, dtype=jnp.int32))
    win = win[:n_pixels]
    valid = win < N
    return jnp.where(valid, win, 0), valid


def warp_count_map(counts: jnp.ndarray, depth: jnp.ndarray, cam_src, cam_dst,
                   ns_full: int, margin: int = 1, projection=None):
    """Warp a Phase-I sample-count map from cam_src to cam_dst.

    Conservative by construction: contributors reduce by max, disoccluded
    pixels (no contributor) get the full count ``ns_full`` (the probe never
    saw their content), and an optional ``margin``-radius max-dilation of
    the warped map absorbs the <=0.5 px registration error of the
    round-to-nearest splat.  Returns (counts (H*W,) int32, valid mask).

    ``projection`` — a precomputed ``forward_warp(cam_src, cam_dst, depth)``
    result, so a caller warping several maps between the same pose pair
    (probe.py warps counts AND opacity/depth per hit) projects once.
    """
    H, W = cam_dst.height, cam_dst.width
    with trace_lib.span("warp.count_map", pixels=H * W):
        tgt, ok, _ = (projection if projection is not None
                      else forward_warp(cam_src, cam_dst, depth))
        warped, valid = scatter_max(counts, tgt, ok, H * W, fill=0)
        warped = jnp.where(valid, warped, ns_full)
        if margin > 0:
            from ..core import adaptive
            warped = adaptive.dilate_count_map(warped, (H, W), margin,
                                               border_fill=ns_full)
        return warped, valid


def warp_image(rgb: jnp.ndarray, acc: jnp.ndarray, depth: jnp.ndarray,
               cam_src, cam_dst, background: float = 1.0):
    """Warp a finished radiance frame (rgb (H*W,3), acc, depth) to cam_dst.

    Z-buffered nearest-surface warp; disoccluded pixels come back as
    ``background`` rgb / zero acc / FAR depth with valid=False — the caller
    marches exactly those rays through Phase II and composites.
    Returns (rgb, acc, depth, valid), all in the destination frame.
    """
    H, W = cam_dst.height, cam_dst.width
    with trace_lib.span("warp.image", pixels=H * W):
        tgt, ok, dist = forward_warp(cam_src, cam_dst, depth)
        src, valid = nearest_source(tgt, ok, dist, H * W)
        rgb_w = jnp.where(valid[:, None], rgb[src], background)
        acc_w = jnp.where(valid, acc[src], 0.0)
        depth_w = jnp.where(valid, dist[src], scene.FAR)
        return rgb_w, acc_w, depth_w, valid
