"""What decides ``correct``: served pixels against the plain reference.

After the window, a sample of pixels of every served frame, drawn from
the seed, is rendered again by the reference (``bench/reference``) from
the same camera and the same seeded weights, and two numbers are
compared with the cell's limits (``bench/limits/<workload>.json``):

* ``rmse``: root mean square of (served - reference) over every sampled
  pixel and channel of every frame;
* ``max_abs``: the widest gap of any sampled pixel and channel.

A number whose limit is null is reported and not held.  A frame that is
missing, of the wrong shape or not finite fails the run.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
NUMBERS = ("rmse", "max_abs")


def sample_pixels(seed: int, rid: int, H: int, W: int, k: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 2, rid])
    return np.sort(rng.choice(H * W, size=min(k, H * W), replace=False))


def rays(reference, frames, seed: int, k: int):
    """Sampled pixels of ``frames`` [(rid, camera, image)]: (origins,
    dirs, served rgb), concatenated over frames."""
    if not frames:
        z = np.zeros((0, 3), np.float32)
        return z, z, z
    os_, ds, served = [], [], []
    for rid, cam, img in frames:
        H, W = cam[0], cam[1]
        pix = sample_pixels(seed, rid, H, W, k)
        o, d = reference.camera_rays(cam, pix)
        os_.append(o)
        ds.append(d)
        served.append(np.asarray(img, np.float64).reshape(H * W, 3)[pix])
    return np.concatenate(os_), np.concatenate(ds), np.concatenate(served)


def readings(served, ref) -> dict:
    if len(served) == 0:
        return {"rmse": None, "max_abs": None}
    err = np.asarray(served, np.float64) - np.asarray(ref, np.float64)
    return {"rmse": float(np.sqrt(np.mean(err ** 2))), "max_abs": float(np.max(np.abs(err)))}


def limits(workload: str, bdir: Path = HERE) -> dict:
    """{number: limit or None}; every number unlimited if the cell has no
    limits file yet."""
    path = bdir / "limits" / f"{workload}.json"
    if not path.exists():
        return dict.fromkeys(NUMBERS)
    spec = json.loads(path.read_text())
    return {n: spec[n]["limit"] for n in NUMBERS}


def judge(numbers: dict, lim: dict, failed: int) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct when no frame failed,
    some number is held, and every held number is within its limit."""
    checks = {"failed_frames": {"value": failed, "limit": 0}}
    ok = failed == 0
    held = False
    for n in NUMBERS:
        v, lm = numbers.get(n), lim.get(n)
        checks[n] = {"value": v, "limit": lm}
        if lm is not None:
            held = True
            ok = ok and v is not None and np.isfinite(v) and v <= lm
    return bool(ok and held), checks
