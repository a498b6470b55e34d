"""Procedural analytic scenes with exact ground-truth (sigma, color) fields.

The paper evaluates on Synthetic-NeRF blender scenes (Lego, Hotdog, ...)
which are not available offline.  We substitute analytic scenes: smooth
compositions of colored SDF primitives inside the unit cube, with an exact
volume-density field.  Ground-truth images are produced by finely marching
the *analytic* field (no network), so PSNR comparisons between rendering
strategies (full sampling / adaptive / decoupled / naive reduction) are
exact-reference comparisons, matching the paper's claim structure.

Scenes mimic the paper's difficulty mix: "lego"-like structured clutter,
a "hotdog"-like pair of blobs on a plate, and a mostly-empty "mic"-like
scene (many background pixels — where adaptive sampling shines).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import rendering

Field = Callable[[jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]


def _sphere_sdf(p, center, radius):
    return jnp.linalg.norm(p - jnp.asarray(center), axis=-1) - radius


def _box_sdf(p, center, half):
    q = jnp.abs(p - jnp.asarray(center)) - jnp.asarray(half)
    outside = jnp.linalg.norm(jnp.maximum(q, 0.0), axis=-1)
    inside = jnp.minimum(jnp.max(q, axis=-1), 0.0)
    return outside + inside


def _primitives_to_field(prims, sharpness=60.0, density_scale=40.0) -> Field:
    """Soft-min composition: density = scale * sigmoid(-sharpness * sdf)."""

    def field(p):
        sds, cols = [], []
        for kind, args, color in prims:
            if kind == "sphere":
                sds.append(_sphere_sdf(p, *args))
            else:
                sds.append(_box_sdf(p, *args))
            cols.append(jnp.asarray(color))
        sd = jnp.stack(sds, axis=-1)  # (N, P)
        occ = jax.nn.sigmoid(-sharpness * sd)  # (N, P)
        sigma = density_scale * jnp.max(occ, axis=-1)
        w = jax.nn.softmax(-sharpness * sd, axis=-1)  # color of nearest prim
        color = w @ jnp.stack(cols, axis=0)
        return sigma, jnp.clip(color, 0.0, 1.0)

    return field


def make_scene(name: str = "lego") -> Field:
    if name == "lego":
        prims = [
            ("box", ((0.5, 0.5, 0.28), (0.26, 0.26, 0.03)), (0.85, 0.75, 0.2)),
            ("box", ((0.42, 0.5, 0.38), (0.06, 0.18, 0.07)), (0.9, 0.6, 0.1)),
            ("box", ((0.62, 0.46, 0.40), (0.05, 0.05, 0.10)), (0.8, 0.2, 0.1)),
            ("sphere", ((0.56, 0.62, 0.50), 0.07), (0.2, 0.4, 0.85)),
            ("sphere", ((0.40, 0.38, 0.52), 0.05), (0.2, 0.8, 0.3)),
            ("box", ((0.52, 0.52, 0.56), (0.03, 0.12, 0.03)), (0.7, 0.7, 0.75)),
        ]
        return _primitives_to_field(prims)
    if name == "hotdog":
        prims = [
            ("box", ((0.5, 0.5, 0.3), (0.3, 0.3, 0.02)), (0.95, 0.95, 0.92)),
            ("sphere", ((0.42, 0.5, 0.4), 0.1), (0.75, 0.45, 0.2)),
            ("sphere", ((0.58, 0.5, 0.4), 0.1), (0.75, 0.45, 0.2)),
            ("box", ((0.5, 0.5, 0.44), (0.16, 0.04, 0.03)), (0.85, 0.25, 0.1)),
        ]
        return _primitives_to_field(prims, sharpness=50.0)
    if name == "mic":  # mostly empty — background-heavy like the paper's Mic
        prims = [
            ("sphere", ((0.5, 0.5, 0.62), 0.08), (0.6, 0.6, 0.65)),
            ("box", ((0.5, 0.5, 0.42), (0.015, 0.015, 0.13)), (0.3, 0.3, 0.32)),
            ("box", ((0.5, 0.5, 0.28), (0.07, 0.07, 0.012)), (0.25, 0.25, 0.28)),
        ]
        return _primitives_to_field(prims, sharpness=80.0)
    raise ValueError(f"unknown scene {name!r}")


@dataclasses.dataclass(frozen=True)
class Camera:
    height: int
    width: int
    focal: float  # in pixels
    # camera-to-world rotation (3,3) and origin (3,)
    c2w_rot: np.ndarray
    origin: np.ndarray


def look_at_camera(
    height: int, width: int, theta: float, phi: float, radius: float = 1.2,
    center=(0.5, 0.5, 0.42), fov_deg: float = 45.0,
) -> Camera:
    center = np.asarray(center, np.float32)
    eye = center + radius * np.asarray(
        [np.cos(phi) * np.cos(theta), np.cos(phi) * np.sin(theta), np.sin(phi)],
        np.float32,
    )
    fwd = center - eye
    fwd = fwd / np.linalg.norm(fwd)
    right = np.cross(fwd, np.asarray([0.0, 0.0, 1.0], np.float32))
    right = right / np.linalg.norm(right)
    up = np.cross(right, fwd)
    rot = np.stack([right, up, fwd], axis=-1).astype(np.float32)  # cols
    focal = 0.5 * width / np.tan(0.5 * np.deg2rad(fov_deg))
    return Camera(height, width, float(focal), rot, eye.astype(np.float32))


def camera_rays(cam: Camera):
    """Returns (origins (H*W, 3), dirs (H*W, 3)) — dirs are unit vectors."""
    j, i = jnp.meshgrid(
        jnp.arange(cam.height, dtype=jnp.float32),
        jnp.arange(cam.width, dtype=jnp.float32),
        indexing="ij",
    )
    x = (i - cam.width * 0.5 + 0.5) / cam.focal
    y = -(j - cam.height * 0.5 + 0.5) / cam.focal
    d_cam = jnp.stack([x, y, jnp.ones_like(x)], axis=-1)  # (H, W, 3)
    rot = jnp.asarray(cam.c2w_rot)
    # full float32: a TPU's default matmul precision rounds the operands
    # to bfloat16, which bends every ray by ~2^-9 rad
    d = jnp.matmul(d_cam, rot.T, precision=jax.lax.Precision.HIGHEST)
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    o = jnp.broadcast_to(jnp.asarray(cam.origin), d.shape)
    return o.reshape(-1, 3), d.reshape(-1, 3)


# Ray-march bounds: scenes live in the unit cube; near/far fixed.
NEAR, FAR = 0.2, 2.2


def sample_ts(n_rays: int, n_samples: int, key=None):
    """(n_rays, n_samples) sample distances: stratified (if key) or
    midpoint sampling of n_samples along each ray."""
    edges = jnp.linspace(NEAR, FAR, n_samples + 1)
    mids = 0.5 * (edges[:-1] + edges[1:])
    if key is not None:
        jitter = (jax.random.uniform(key, (n_rays, n_samples)) - 0.5) * (
            (FAR - NEAR) / n_samples
        )
        return mids[None, :] + jitter
    return jnp.broadcast_to(mids[None, :], (n_rays, n_samples))


def points_at(origins, dirs, ts):
    """Sample points (R, S, 3) and deltas (R, S) at distances ts (R, S)."""
    n_samples = ts.shape[-1]
    deltas = jnp.full(ts.shape, (FAR - NEAR) / n_samples)
    pts = origins[:, None, :] + ts[..., None] * dirs[:, None, :]
    return pts, deltas


def sample_points(origins, dirs, n_samples: int, key=None):
    """Stratified (if key) or midpoint sampling of n_samples along each ray.

    Returns points (R, S, 3), deltas (R, S), ts (R, S).
    """
    ts = sample_ts(origins.shape[0], n_samples, key)
    pts, deltas = points_at(origins, dirs, ts)
    return pts, deltas, ts


@partial(jax.jit, static_argnums=(0, 3))
def render_reference(field: Field, origins, dirs, n_samples: int = 512):
    """Ground-truth render by finely marching the analytic field."""
    pts, deltas, _ = sample_points(origins, dirs, n_samples)
    flat = pts.reshape(-1, 3)
    sigma, color = field(flat)
    # points outside the unit cube contribute nothing
    inside = jnp.all((flat >= 0.0) & (flat <= 1.0), axis=-1)
    sigma = jnp.where(inside, sigma, 0.0)
    sigma = sigma.reshape(pts.shape[:2])
    color = color.reshape(pts.shape[:2] + (3,))
    rgb, acc, _ = rendering.composite(sigma, color, deltas)
    return rgb, acc
