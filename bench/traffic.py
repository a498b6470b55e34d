"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its requests from the seed.

A request is ``(rid, camera, arrival_s)`` with ``camera = (H, W, focal,
rot (3,3) with columns right/up/forward, eye (3,))``; ``arrival_s`` is
seconds after the window opens (0 for a closed batch).

Views (``views.kind``):
  * ``fibonacci``: distinct points of an N-point Fibonacci sphere at the
    orbit radius, in an order shuffled by the seed (no view repeats);
  * ``orbits``: ``users`` viewers, each on a smooth orbit of
    ``step_rad`` a frame, from start poses drawn from the seed at least
    ``min_start_sep_deg`` apart.

Arrivals (``loop.kind``):
  * ``closed``: every frame queued at once; the harness gives the count;
  * ``open``: each user sends on a fixed cadence at ``rate_per_s / users``
    with phases spread evenly over a period and a +-``jitter`` share of a
    period drawn from a fixed stream, so every seed offers the same
    arrivals; frames due in ``[0, seconds)`` are sent.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str, bdir: Path = HERE) -> dict:
    path = bdir / "traffic" / f"{name}.json"
    spec = json.loads(path.read_text())
    spec["name"] = name
    return spec


def look_at(spec: dict, theta: float, phi: float):
    """Pinhole camera on the orbit sphere looking at its centre, z up."""
    H, W = spec["frame"]
    v = spec["views"]
    center = np.asarray(v["center"], np.float64)
    eye = center + v["radius"] * np.asarray(
        [math.cos(phi) * math.cos(theta), math.cos(phi) * math.sin(theta), math.sin(phi)])
    fwd = (center - eye) / np.linalg.norm(center - eye)
    right = np.cross(fwd, [0.0, 0.0, 1.0])
    right /= np.linalg.norm(right)
    up = np.cross(right, fwd)
    rot = np.stack([right, up, fwd], axis=-1).astype(np.float32)
    focal = 0.5 * W / math.tan(0.5 * math.radians(v["fov_deg"]))
    return (H, W, float(focal), rot, eye.astype(np.float32))


def fibonacci(n: int):
    """(theta, phi) of the n points of a Fibonacci sphere."""
    k = np.arange(n) + 0.5
    z = 1.0 - 2.0 * k / n
    theta = np.pi * (1.0 + 5.0 ** 0.5) * k
    return np.mod(theta, 2 * np.pi), np.arcsin(z)


def _angle(a, b) -> float:
    """Angle between two (theta, phi) directions on the sphere."""
    ta, pa = a
    tb, pb = b
    c = (math.sin(pa) * math.sin(pb) + math.cos(pa) * math.cos(pb) * math.cos(ta - tb))
    return math.acos(max(-1.0, min(1.0, c)))


def _start_poses(v: dict, rng, users: int):
    """Start (theta, phi) per user, pairwise at least min_start_sep_deg."""
    sep = math.radians(v["min_start_sep_deg"])
    lo, hi = (math.radians(x) for x in v["phi_deg"])
    starts = []
    while len(starts) < users:
        p = (rng.uniform(0, 2 * math.pi), rng.uniform(lo, hi))
        if all(_angle(p, q) >= sep for q in starts):
            starts.append(p)
    return starts


def warm_up(spec: dict, seed: int = 7):
    """Two cameras that take the paths the mix takes: two distinct views,
    or one viewer's first two poses on its orbit."""
    if spec["views"]["kind"] == "fibonacci":
        return [cam for _, cam, _ in requests(spec, seed, 0.0, frames=2)]
    v = spec["views"]
    th, ph = _start_poses(v, np.random.default_rng([seed, 1]), 1)[0]
    return [look_at(spec, th, ph), look_at(spec, th + v["step_rad"], ph)]


def requests(spec: dict, seed: int, seconds: float, frames: int | None = None):
    """The mix's requests for one run: [(rid, camera, arrival_s)]."""
    rng = np.random.default_rng([seed, 1])
    v, loop = spec["views"], spec["loop"]
    if loop["kind"] == "closed":
        if v["kind"] != "fibonacci":
            raise ValueError(f"closed loop over {v['kind']!r} views")
        theta, phi = fibonacci(v["points"])
        if frames > v["points"]:
            raise ValueError(f"{frames} frames but {v['points']} distinct views")
        pick = rng.permutation(v["points"])[:frames]
        return [(i, look_at(spec, theta[p], phi[p]), 0.0) for i, p in enumerate(pick)]
    if loop["kind"] != "open" or v["kind"] != "orbits":
        raise ValueError(f"unknown mix {loop['kind']!r} over {v['kind']!r}")
    users = v["users"]
    starts = _start_poses(v, rng, users)
    sense = rng.choice([-1.0, 1.0], size=users)
    period = users / loop["rate_per_s"]
    fixed = np.random.default_rng(loop["arrival_stream"])
    out = []
    for u in range(users):
        k = 0
        while True:
            due = period * (u / users + k + fixed.uniform(-loop["jitter"], loop["jitter"]))
            if due >= seconds:
                break
            if due >= 0.0:
                th = starts[u][0] + sense[u] * v["step_rad"] * k
                out.append((due, u, look_at(spec, th, starts[u][1])))
            k += 1
    out.sort(key=lambda x: (x[0], x[1]))
    return [(i, cam, due) for i, (due, _u, cam) in enumerate(out)]
