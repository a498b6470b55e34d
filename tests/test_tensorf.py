"""TensoRF's VM field (core/tensorf.py) at SMOKE size on seeded random
weights: its sigma and rgb against the plain reference's equations
(bench/reference/tensorf.py), the packed encode against the one-gather-
a-corner formulation, frames served by RenderServingEngine against the
reference render, a bfloat16 field failing the same tolerance, the packed
tables passed to the march as arguments, and a tiny benchmark cell judged
correct by the harness."""
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.tensorf_vm192 import CONFIG, SMOKE
from repro.core import fields, pipeline, scene, tensorf
from repro.serve import pool
from repro.serve.render_engine import (RenderRequest, RenderServeConfig,
                                       RenderServingEngine)

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import tensorf as reference  # noqa: E402

# planes and lines N(0, 1.6^2): at SMOKE's 24^3 grid the 16x16 views are
# then ~0.66 opaque on average and not one color
SCALE = 1.6
# Frames: the program's float32 field against the float32 reference at
# `highest` precision differs only by rounding (the two compute the grid
# coordinate and sum the components in different orders); measured
# ~1e-6 rmse, ~2e-5 at worst on one pixel.  A field whose tables are
# rounded to bfloat16 reads ~1e-3 rmse, the bfloat16 reference ~4e-3.
RMSE_TOL, MAX_ABS_TOL = 2e-5, 2e-4


def ref_cfg(bundle, scale=SCALE):
    m, a = bundle.model, bundle.asdr
    lo = -tensorf.BOX_SIZE / 2
    return {"tensorf": {"grid": m.grid, "n_lamb_sigma": m.density_components,
                        "n_lamb_sh": m.appearance_components,
                        "data_dim_color": m.feature_dim, "fea_pe": tensorf.FEATURE_PE,
                        "view_pe": tensorf.VIEW_PE, "featureC": m.hidden,
                        "density_shift": tensorf.DENSITY_SHIFT,
                        "distance_scale": tensorf.DISTANCE_SCALE, "aabb": [lo, -lo]},
            "asdr": {"ns_full": a.ns_full, "chunk": a.chunk, "group": a.group},
            "scene": {"near": scene.NEAR, "far": scene.FAR},
            "weights": {"scale": scale}}


@pytest.fixture(scope="module")
def params():
    return tensorf.init_params(jax.random.PRNGKey(3), SMOKE.model, scale=SCALE)


def test_config_is_published_widths():
    m = CONFIG.model
    assert (m.grid, m.density_components, m.appearance_components) == (300, 16, 48)
    assert m.mlp_sizes == (150, 128, 128, 3) and m.feature_dim == 27
    assert CONFIG.asdr.ns_full == 1196 and SMOKE.asdr.ns_full % SMOKE.asdr.chunk


def test_field_matches_reference_equations(params):
    """sigma and rgb at random points inside and outside the box.  Only
    rounding separates them (rtol 1e-5 on sigma, which reaches hundreds
    where softplus is steep; 1e-5 absolute on colors)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    pts = jax.random.uniform(k1, (2048, 3), minval=-0.25, maxval=1.25)
    d = jax.random.normal(k2, (2048, 3))
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    fns = tensorf.field_fns(params, SMOKE.model)
    sigma, geo = fns.density(pts)
    cfg = ref_cfg(SMOKE)
    with jax.default_matmul_precision("highest"):
        want_sigma = reference.sigma(cfg, params, pts)
        want_rgb = reference.rgb(cfg, params, pts, d)
    inside = np.all((np.asarray(pts) >= 0) & (np.asarray(pts) <= 1), axis=-1)
    assert 0.2 < inside.mean() < 0.8
    assert np.all(np.asarray(sigma)[~inside] == 0)
    assert np.asarray(sigma)[inside].max() > 1.0          # not an empty box
    np.testing.assert_allclose(sigma, want_sigma, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(geo, pts)
    np.testing.assert_allclose(fns.color(geo, d), want_rgb, rtol=0, atol=1e-5)


def test_density_spreads_its_reads_outside_the_box(params, monkeypatch):
    """Points outside the box reach the density tables at coordinates
    inside it, away from the border rows a clamp would pile them on;
    points inside are read where they are."""
    seen = []
    real = tensorf.vm_features
    monkeypatch.setattr(tensorf, "vm_features",
                        lambda x, t, n: seen.append(np.asarray(x)) or real(x, t, n))
    pts = jnp.asarray([[0.25, 0.5, 1.0], [1.3, 0.5, 0.5], [-0.2, 1.6, 0.5], [1.9, 1.9, -0.6]])
    sigma, _ = tensorf.field_fns(params, SMOKE.model).density(pts)
    (x,) = seen
    np.testing.assert_array_equal(x[0], pts[0])
    np.testing.assert_allclose(x[1:], [[0.3, 0.5, 0.5], [0.8, 0.6, 0.5], [0.9, 0.9, 0.4]],
                               atol=1e-6)
    assert np.all(np.asarray(sigma)[1:] == 0)


def features_18(points, side, n):
    """The oracle: ``vm_features`` over the unpacked tables, one gather a
    plane corner and a line end (18 indices a point), in the same order
    of operations."""
    R = side["planes"].shape[-1]
    g = jnp.clip(points.astype(jnp.float32), 0.0, 1.0) * (n - 1)
    base = jnp.minimum(jnp.floor(g), n - 2).astype(jnp.int32)
    frac = g - base
    planes = side["planes"].reshape(3 * n * n, R)
    lines = side["lines"].reshape(3 * n, R)
    out = []
    for m, ((a, b), c) in enumerate(zip(tensorf.PLANE_AXES, tensorf.LINE_AXES)):
        fa, fb, fc = frac[:, a:a + 1], frac[:, b:b + 1], frac[:, c:c + 1]
        row = m * n * n + base[:, a] * n + base[:, b]
        p = (planes[row] * ((1 - fa) * (1 - fb)) + planes[row + 1] * ((1 - fa) * fb)
             + planes[row + n] * (fa * (1 - fb)) + planes[row + n + 1] * (fa * fb))
        line = m * n + base[:, c]
        v = lines[line] * (1 - fc) + lines[line + 1] * fc
        out.append(p * v)
    return jnp.stack(out, axis=1)


def _points(where, n):
    """Test points: inside the box, on its faces and corners, in the last
    voxel of an axis (``base == n - 2``), and past the box as the density
    side reads them (modulo the box)."""
    k = jax.random.PRNGKey(5)
    if where == "inside":
        return jax.random.uniform(k, (512, 3))
    if where == "faces":
        x = jax.random.uniform(k, (512, 3))
        axis = jnp.arange(512) % 3
        return x.at[jnp.arange(512), axis].set(jnp.arange(512) // 3 % 2 * 1.0)
    if where == "last_voxel":
        lo = (n - 2) / (n - 1)
        return jax.random.uniform(k, (512, 3), minval=lo, maxval=1.0).at[:8].set(1.0)
    return jnp.mod(jax.random.uniform(k, (512, 3), minval=-0.5, maxval=1.5), 1.0)


@pytest.mark.parametrize("side", ["density", "appearance"])
@pytest.mark.parametrize("where", ["inside", "faces", "last_voxel", "past_box"])
def test_packed_features_are_bit_identical(params, side, where):
    """The packed encode (6 rows a point) equals the 18-gather one bit for
    bit on the CPU."""
    n = SMOKE.model.grid
    x = _points(where, n)
    if where == "last_voxel":
        assert np.all(np.floor(np.asarray(x) * (n - 1)) >= n - 2)
    packed = tensorf.pack_tables(params)[side]
    got = jax.jit(lambda p, t: tensorf.vm_features(p, t, n))(x, packed)
    want = jax.jit(lambda p, t: features_18(p, t, n))(x, params[side])
    assert np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_field_carries_packed_tables(params, monkeypatch):
    """``field_fns`` packs the tables once and binds the packed ones alone;
    a jitted rebind, the pooled march and the probe only read them."""
    n, R = SMOKE.model.grid, SMOKE.model.appearance_components
    fns = tensorf.field_fns(params, SMOKE.model)
    bound = fns.bound.params
    assert bound["appearance"]["corners"].shape == (3, n * n, 4 * R)
    assert bound["appearance"]["ends"].shape == (3, n, 2 * R)
    for side in ("density", "appearance"):
        assert set(bound[side]) == {"corners", "ends"}
        P = np.asarray(params[side]["planes"]).reshape(3, n, n, -1)
        L = np.asarray(params[side]["lines"])
        q = np.asarray(bound[side]["corners"]).reshape(3, n, n, 4, -1)
        e = np.asarray(bound[side]["ends"]).reshape(3, n, 2, -1)
        for k, (da, db) in enumerate(((0, 0), (0, 1), (1, 0), (1, 1))):
            np.testing.assert_array_equal(q[:, :n - 1, :n - 1, k],
                                          P[:, da:n - 1 + da, db:n - 1 + db])
        np.testing.assert_array_equal(e[:, :n - 1], np.stack([L[:, :-1], L[:, 1:]], 2))
    assert bound["basis"] is params["basis"] and bound["mlp"] is params["mlp"]

    calls = []
    real = tensorf.pack_tables
    monkeypatch.setattr(tensorf, "pack_tables", lambda *a: calls.append(a) or real(*a))
    fkey, b = fields.unbind(fns)
    pts = jnp.linspace(0.0, 1.0, 96).reshape(32, 3)
    jax.jit(lambda p, x: fields.rebind(fkey, p).color(x, x))(b, pts)
    acfg = SMOKE.asdr
    z = jnp.zeros((2, acfg.block_size, 3))
    pool.batched_march(fns, acfg)(z, z, jnp.full((2,), acfg.ns_full, jnp.int32))
    pipeline.probe_phase(fns, acfg, scene.look_at_camera(8, 8, 0.7, 0.5))
    assert calls == []


def _serve(p, cams):
    eng = RenderServingEngine({"s": tensorf.field_fns(p, SMOKE.model)}, SMOKE.asdr,
                              RenderServeConfig(slots=2, blocks_per_batch=4, reuse=None))
    reqs = eng.render([RenderRequest(rid=i, scene="s", cam=c) for i, c in enumerate(cams)])
    st = eng.engine_stats()
    eng.close()
    return np.concatenate([np.asarray(r.image).reshape(-1, 3) for r in reqs]), reqs, st


@pytest.fixture(scope="module")
def served(params):
    cams = [scene.look_at_camera(16, 16, theta=0.7 + 0.9 * i, phi=0.5) for i in range(2)]
    o = np.concatenate([np.asarray(scene.camera_rays(c)[0]) for c in cams])
    d = np.concatenate([np.asarray(scene.camera_rays(c)[1]) for c in cams])
    want = reference.render(ref_cfg(SMOKE), params, o, d)
    return cams, o, d, want


def _errors(got, want):
    err = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt(np.mean(err ** 2))), float(np.abs(err).max())


def test_engine_frames_match_reference(params, served):
    """Frames through Stage A, the pool and the march against the plain
    reference render; engine_stats() and the per-frame stats count the
    VM rows and the samples whose color ran."""
    cams, o, d, want = served
    got, reqs, st = _serve(params, cams)
    assert got.std() > 0.05                               # not one color
    rmse, max_abs = _errors(got, want)
    assert rmse < RMSE_TOL and max_abs < MAX_ABS_TOL, (rmse, max_abs)
    assert st["encode_gather_rows_per_sample"] == st["color_gather_rows_per_sample"] == 6
    a = SMOKE.asdr
    anchors = sum(r.stats["samples_processed"] for r in reqs) // a.chunk * (a.chunk // a.group)
    probes = sum(r.stats["probe_samples"] for r in reqs)
    assert probes > 0
    assert st["color_samples"] == sum(r.stats["color_samples"] for r in reqs) == anchors + probes


@pytest.mark.parametrize("control", ["bf16_tables", "bf16_reference"])
def test_bfloat16_fails_the_tolerance(params, served, control):
    """A field served from bfloat16-rounded tables and weights, and the
    reference computed in bfloat16, both miss the frames' tolerance."""
    cams, o, d, want = served
    if control == "bf16_tables":
        low = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
        got = _serve(low, cams)[0]
    else:
        got = reference.render(ref_cfg(SMOKE), params, o, d, dtype=jnp.bfloat16)
    rmse, _ = _errors(got, want)
    assert rmse > 10 * RMSE_TOL, rmse


def _largest_constant_bytes(hlo_text: str) -> int:
    """Bytes of the largest dense hex constant in StableHLO text."""
    import re
    blobs = re.findall(r'dense<"0x([0-9A-Fa-f]*)">', hlo_text)
    return max((len(b) // 2 for b in blobs), default=0)


def test_march_takes_tables_as_arguments():
    """The jitted pooled march holds no constant over 1 MB: the packed
    tables (4.7 MB of appearance corners at a 128^2 plane) are its
    arguments, while a closure over the same field bakes them in."""
    cfg = tensorf.TensoRFConfig(grid=128, density_components=2,
                                appearance_components=6, feature_dim=5, hidden=16)
    p = tensorf.init_params(jax.random.PRNGKey(0), cfg, scale=1.0)
    assert p["appearance"]["planes"].nbytes > 1 << 20
    fns = tensorf.field_fns(p, cfg)
    acfg = SMOKE.asdr
    march = pool.batched_march(fns, acfg)
    z = jnp.zeros((2, acfg.block_size, 3))
    budgets = jnp.full((2,), acfg.ns_full, jnp.int32)
    text = march.func.lower(*march.args, z, z, budgets).as_text()
    assert _largest_constant_bytes(text) < 1 << 20
    fkey, bound = fields.unbind(fns)
    assert bound is fns.bound.params and bound["basis"] is p["basis"]
    assert bound["appearance"]["corners"].nbytes == 4 * p["appearance"]["planes"].nbytes
    baked = jax.jit(lambda x: fns.color(x, x)).lower(z[0]).as_text()
    assert _largest_constant_bytes(baked) > 1 << 20


def test_harness_runs_a_tiny_tensorf_cell_correct(tmp_path):
    """``harness.run`` on a TensoRF cell at SMOKE size on 16x16 fresh views,
    holding the fresh-128 cell's limits, is correct, and the traced run
    reads the VM rows a marched sample (6 + 6 / group = 9)."""
    from bench import harness
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    b = root / "bench"
    cfg = json.loads((b / "configs" / "tensorf-vm192.json").read_text())
    small = ref_cfg(SMOKE)
    cfg["tensorf"].update(small["tensorf"])
    cfg["weights"] = small["weights"]
    a = SMOKE.asdr
    cfg["asdr"].update(ns_full=a.ns_full, probe_stride=a.probe_stride,
                       candidates=list(a.candidates), block_size=a.block_size,
                       chunk=a.chunk)
    (b / "configs" / "tensorf-smoke.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "fresh-128.json").read_text())
    mix.update(frame=[16, 16], check_pixels_per_frame=64)
    (b / "traffic" / "fresh-tiny.json").write_text(json.dumps(mix))
    src, cell = "tensorf-vm192.fresh-128", "tensorf-smoke.fresh"
    shutil.copy(b / "limits" / f"{src}.json", b / "limits" / f"{cell}.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tensorf-smoke", "source": "https://arxiv.org/abs/2203.09517",
                            "file": "bench/configs/tensorf-smoke.json", "reduced": [],
                            "why": "CPU"})
    spec["workloads"].append({"name": cell, "config": "tensorf-smoke",
                              "traffic": "fresh-tiny", "chips": 1, "why": "CPU"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if src in m.get("workloads", []):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for trace in (False, True):
        r = harness.run(cell, 2 ** 31 + 91, 1.0, trace, time.perf_counter(),
                        require_chip=False, root=root)
        assert r["correct"] and r["failed"] == 0, r["checks"]
        if trace:
            assert r["metrics"]["vm_rows_per_sample.tensorf"]["value"] == 9.0
        else:
            assert {"rays_per_s", "setup_s"} <= set(r["metrics"])


def test_work_counts_at_published_widths():
    """bench/work_tensorf.py against hand counts at tensorf-vm192."""
    from bench import work_tensorf
    cfg = json.loads((ROOT / "bench" / "configs" / "tensorf-vm192.json").read_text())
    w = work_tensorf.per_sample(cfg)
    side = lambda R: 3 * (4 + 8 * R + 4 * R + R)     # noqa: E731
    assert w["density_flops"] == side(16) + 48 == 684
    mlp = 2 * (150 * 128 + 128 * 128 + 128 * 3) + 128 + 128 + 3
    assert w["color_flops"] == side(48) + 2 * 144 * 27 + mlp + 27 * 2 + 3 * 2 == 81915
    assert w["march_rows"] == 27 and w["gather_bytes"] == 18 * 64 + 18 * 192 / 2
    assert w["march_flops"] == 684 + 81915 / 2
    t, bound = work_tensorf.march_least_seconds(cfg, 131072, {"hbm_bytes_per_s": 819e9,
                                                              "bf16_flops": 197e12})
    assert bound == "bytes" and abs(t - 131072 * 2880 / 819e9) < 1e-12
