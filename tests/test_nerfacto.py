"""nerfacto (core/nerfacto.py) at SMOKE size on seeded random weights:
its contraction, sampler, field and frames against the plain reference
(bench/reference/nerfacto.py), the interval march against a one-pass
render at the same intervals, the per-field corner-row and x-neighbour
row budget, and a tiny benchmark cell judged correct by the harness."""
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.nerfacto import CONFIG, SMOKE
from repro.core import fields, hashgrid, nerfacto, pipeline, scene
from repro.kernels import ops
from repro.serve import pool
from repro.serve.render_engine import (RenderRequest, RenderServeConfig,
                                       RenderServingEngine)

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import nerfacto as reference  # noqa: E402

SEED = 2 ** 31 + 19
# Frames: the program's float32 field against the float32 reference at
# `highest` precision differs by rounding (corner-row blends, the folded
# appearance row, another inverse-CDF search) and the density kernel's
# clip at exp(15); measured 3.2e-6 rmse, 2.8e-5 at worst on one pixel.
# The bfloat16 reference reads 7.6e-3 rmse (test below).
RMSE_TOL, MAX_ABS_TOL = 2e-5, 2e-4


def _grid(g):
    return {"n_levels": g.n_levels, "log2_table_size": g.log2_table_size,
            "feature_dim": g.feature_dim, "base_resolution": g.base_resolution,
            "max_resolution": g.max_resolution}


def ref_cfg(bundle=SMOKE):
    """The configuration file's form of a bundle, with
    ``bench/configs/nerfacto.json``'s weights."""
    m, a = bundle.model, bundle.asdr
    cfg = json.loads((ROOT / "bench" / "configs" / "nerfacto.json").read_text())
    cfg["grid"] = _grid(m.grid)
    cfg["proposal_nets"] = [dict(_grid(g), hidden_dim=16, num_layers=2)
                            for g in m.proposal_grids]
    cfg["mlp"].update(density_hidden=m.net.density_hidden, color_hidden=m.net.color_hidden)
    cfg["sampler"].update(num_proposal_samples_per_ray=list(m.samples[:-1]),
                          num_nerf_samples_per_ray=m.samples[-1])
    cfg["asdr"].update(ns_full=a.ns_full, candidates=list(a.candidates),
                       block_size=a.block_size, chunk=a.chunk)
    return cfg


@pytest.fixture(scope="module")
def params():
    return reference.make_params(ref_cfg(), SEED)


@pytest.fixture(scope="module")
def field(params):
    return nerfacto.field_fns(params, SMOKE.model)


def _rays(n, seed=3):
    cam = scene.look_at_camera(16, 16, theta=0.4 + seed, phi=0.5)
    o, d = scene.camera_rays(cam)
    return o[:n], d[:n]


def test_config_is_published_widths():
    cfg = json.loads((ROOT / "bench" / "configs" / "nerfacto.json").read_text())
    dens, col, props = reference.mlp_sizes(cfg)
    assert dens == [32, 64, 16] and col == [63, 64, 64, 3]
    assert props == [[10, 16, 1], [10, 16, 1]] and cfg["reduced"] == []
    m = CONFIG.model
    assert m.grid == hashgrid.HashGridConfig() and m.samples == (256, 96, 48)
    assert [g.max_resolution for g in m.proposal_grids] == [128, 256]
    assert m.net.color_input_dim == 31 and CONFIG.asdr.group == 1


def test_contraction_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(1), (4096, 3)) * jnp.asarray([0.3, 3.0, 300.0])
    got, want = nerfacto.contract(x), reference.contract(x)
    np.testing.assert_array_equal(got, want)
    n = np.abs(np.asarray(x)).max(-1)
    assert np.all(np.abs(np.asarray(got)).max(-1) < 2.0)
    np.testing.assert_array_equal(np.asarray(got)[n <= 1], np.asarray(x)[n <= 1])
    np.testing.assert_allclose(np.abs(np.asarray(got)).max(-1)[n > 1], 2 - 1 / n[n > 1],
                               rtol=1e-6)


def test_initial_edges_span_near_to_far():
    cfg = ref_cfg()
    s = jnp.linspace(0.0, 1.0, 257)
    t = nerfacto.world_distance(s)
    np.testing.assert_array_equal(t, reference.spacing_to_world(cfg, s))
    np.testing.assert_allclose([t[0], t[-1]], [nerfacto.NEAR, nerfacto.FAR], rtol=1e-4)
    assert np.all(np.diff(np.asarray(t)) > 0)


def test_pdf_resampling_matches_reference():
    """Resampled edges are monotone, start at 0-ish and end near 1, and
    equal the reference's (jnp.searchsorted) to 1e-6."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    s = jnp.sort(jax.random.uniform(k1, (64, 41)), axis=-1).at[:, 0].set(0.0).at[:, -1].set(1.0)
    w = jax.random.uniform(k2, (64, 40)) ** 8
    w = w.at[:4].set(0.0)                          # rays with no weight
    got = nerfacto.pdf_edges(s, w, 24)
    want = reference.pdf_edges(ref_cfg(), s, w, 24)
    assert got.shape == (64, 25)
    assert np.all(np.diff(np.asarray(got), axis=-1) >= 0)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_sampler_matches_reference(field, params):
    """The proposal sampler's edges for real rays equal the reference's
    to ~1e-6 in spacing space."""
    o, d = _rays(128)
    got = field.sampler.edges(o, d)
    o_w = (o - jnp.asarray(nerfacto.CENTER)) / nerfacto.WORLD_SCALE
    with jax.default_matmul_precision("highest"):
        want = reference.sample(ref_cfg(), params, o_w, d)
    assert got.shape == (128, SMOKE.model.samples[-1] + 1)
    assert np.all(np.diff(np.asarray(got), axis=-1) > 0)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    assert field.sampler.samples_per_ray == 64 + 24


def test_field_matches_reference(field, params):
    """sigma (per program unit) and rgb at world points near and far."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    x_w = jax.random.normal(k1, (1024, 3)) * 2.0
    d = jax.random.normal(k2, (1024, 3))
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    pts = x_w * nerfacto.WORLD_SCALE + jnp.asarray(nerfacto.CENTER)
    sigma, geo = field.density(pts)
    cfg = ref_cfg()
    lv = tuple(reference.levels(cfg))
    with jax.default_matmul_precision("highest"):
        want_sigma, want_geo = reference._grid_field(
            params["grid"], params["mlps"]["density"], lv, x_w, jnp.float32)
        e = jnp.mean(params["appearance"], axis=0)
        h = jnp.concatenate([reference._sh4(d, jnp.float32), want_geo,
                             jnp.broadcast_to(e, (1024, e.shape[0]))], axis=-1)
        want_rgb = jax.nn.sigmoid(reference._mlp(params["mlps"]["color"], h, jnp.float32))
    # the density kernel clips h_0 at 15 before exp (Instant-NGP's
    # trunc_exp); nerfstudio's forward pass does not: a departure
    got, want = np.asarray(sigma) * nerfacto.WORLD_SCALE, np.asarray(want_sigma)
    clipped = want > np.exp(15.0)
    assert 0 < clipped.mean() < 0.5
    # h_0 sums products of features up to +-30 (the weights' scale): its
    # rounding reaches ~3e-4, so sigma = exp(h_0) agrees to that relatively
    np.testing.assert_allclose(got[~clipped], want[~clipped], rtol=5e-4)
    np.testing.assert_allclose(got[clipped], np.exp(15.0), rtol=1e-5)
    # the same rounding in geo carries into the head: colors to 5e-5
    np.testing.assert_allclose(field.color(geo, d), want_rgb, rtol=0, atol=5e-5)
    assert np.asarray(want_rgb).std() > 0.02


def test_field_from_xrow_levels_equals_field_without(field, params, monkeypatch):
    """On a device whose memory leaves main levels 4-7 to x-neighbour
    rows (4 gathered rows a point there, not 8), the field's density and
    the proposal sampler's edges are the same to the bit, and
    gather_stats counts the rows."""
    monkeypatch.setattr(ops, "device_memory_bytes", lambda x: 240 * 10 ** 6)
    xfield = nerfacto.field_fns(params, SMOKE.model)
    bound = xfield.bound.params
    assert (len(bound["corners"]), len(bound["xrows"])) == (4, 4)
    st = fields.gather_stats(xfield)
    assert (st["xrow_levels"], st["encode_gather_rows_per_sample"]) == (4, 4 + 4 * 4)
    assert fields.gather_stats(field)["xrow_levels"] == 0
    x_w = jax.random.normal(jax.random.PRNGKey(12), (1024, 3)) * 2.0
    pts = x_w * nerfacto.WORLD_SCALE + jnp.asarray(nerfacto.CENTER)
    for got, want in zip(xfield.density(pts), field.density(pts)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    o, d = _rays(128)
    np.testing.assert_array_equal(np.asarray(xfield.sampler.edges(o, d)),
                                  np.asarray(field.sampler.edges(o, d)))


def test_interval_march_matches_one_pass_render(field):
    """The pooled march at the sampler's intervals (chunks of 8 over 12
    samples, the last one cut short) equals ``pipeline.render_at`` at the
    same midpoints and widths."""
    o, d = _rays(2 * SMOKE.asdr.block_size)
    edges = field.sampler.edges(o, d)
    t = nerfacto.program_distance(edges)
    rgb, aux = pipeline.render_at(field, o, d, 0.5 * (t[:, 1:] + t[:, :-1]),
                                  deltas=t[:, 1:] - t[:, :-1])
    a = SMOKE.asdr
    march = pool.batched_march(field, a)
    out = march(o.reshape(2, -1, 3), d.reshape(2, -1, 3),
                jnp.full((2,), a.ns_full, jnp.int32), edges.reshape(2, a.block_size, -1))
    # the march's exclusive cumsum of log steps is a running sum less the
    # step, which rounds where alpha saturates (steps of log 1e-10): ~4e-5
    np.testing.assert_allclose(out[0].reshape(-1, 3), rgb, rtol=0, atol=1e-4)
    np.testing.assert_allclose(out[1].reshape(-1), aux["acc"], rtol=0, atol=1e-4)
    assert np.all(np.asarray(out[3]) == 2)          # ceil(12 / 8) chunks a block


def _serve(params, cams):
    eng = RenderServingEngine({"s": nerfacto.field_fns(params, SMOKE.model)}, SMOKE.asdr,
                              RenderServeConfig(slots=2, blocks_per_batch=4, reuse=None))
    reqs = eng.render([RenderRequest(rid=i, scene="s", cam=c) for i, c in enumerate(cams)])
    st = eng.engine_stats()
    eng.close()
    order = sorted(reqs, key=lambda r: r.rid)
    return np.concatenate([np.asarray(r.image).reshape(-1, 3) for r in order]), order, st


@pytest.fixture(scope="module")
def served(params):
    cams = [scene.look_at_camera(16, 16, theta=0.7 + 0.9 * i, phi=0.5) for i in range(2)]
    o = np.concatenate([np.asarray(scene.camera_rays(c)[0]) for c in cams])
    d = np.concatenate([np.asarray(scene.camera_rays(c)[1]) for c in cams])
    want, acc = reference.render(ref_cfg(), params, o, d, with_acc=True)
    return cams, o, d, want, acc


def _errors(got, want):
    err = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt(np.mean(err ** 2))), float(np.abs(err).max())


def test_engine_frames_match_reference(params, served):
    """Frames through Stage A's proposal sampler, the pool and the
    interval march against the plain reference; engine_stats() counts the
    proposal samples and rows."""
    cams, o, d, want, acc = served
    got, reqs, st = _serve(params, cams)
    assert got.std() > 0.05 and acc.mean() > 0.5         # neither one color nor empty
    rmse, max_abs = _errors(got, want)
    assert rmse < RMSE_TOL and max_abs < MAX_ABS_TOL, (rmse, max_abs)
    assert st["proposal_samples_per_ray"] == 64 + 24
    # SMOKE on the CPU: the res^3 <= T levels of each grid from corner rows
    lv = hashgrid.field_corner_levels(SMOKE.model.proposal_grids)
    assert [len(x) for x in lv] == [1, 1]
    assert st["proposal_gather_rows_per_ray"] == 64 * (1 + 8 * 2) + 24 * (1 + 8 * 2)
    assert all(r.stats["probe_samples"] == 256 * 88 for r in reqs)


def test_bfloat16_reference_fails_the_tolerance(params, served):
    cams, o, d, want, _ = served
    low = reference.render(ref_cfg(), params, o, d, dtype=jnp.bfloat16)
    rmse, _ = _errors(low, want)
    assert rmse > 10 * RMSE_TOL, rmse


def test_probe_reuse_is_refused(field):
    with pytest.raises(ValueError, match="sampler"):
        RenderServingEngine({"s": field}, SMOKE.asdr, RenderServeConfig())


def test_corner_budget_is_shared_over_the_field_grids(monkeypatch):
    """On a 16 GB v5e (bytes_limit 16,909,336,064) nerfacto's three grids
    share a quarter: main levels 0-9, both proposal grids whole, 4.0 GB;
    Instant-NGP's one grid keeps levels 0-9."""
    mem = 16909336064
    m = CONFIG.model
    grids = (m.grid,) + m.proposal_grids
    levels = hashgrid.field_corner_levels(grids, mem)
    assert levels == (tuple(range(10)), tuple(range(5)), tuple(range(5)))
    sizes = [sum(hashgrid.corner_bytes(g, l) for l in lv) for g, lv in zip(grids, levels)]
    assert sizes == [2605597696, 166409984, 1227096064] and sum(sizes) <= mem // 4
    assert hashgrid.field_corner_levels((m.grid,), mem) == (tuple(range(10)),)
    assert hashgrid.corner_levels(m.grid, mem) == tuple(range(10))
    # a smaller device leaves out the largest rows first: main level 9
    # (1.63 GB) before the 256^3 proposal level (1.07 GB)
    assert hashgrid.field_corner_levels(grids, 12 << 30) == (
        tuple(range(9)), tuple(range(5)), tuple(range(5)))

    # the field's build asks ops for the three grids' rows under one budget
    seen = []
    real = ops.corner_tables
    monkeypatch.setattr(ops, "device_memory_bytes", lambda x: 4 * 60 * 10 ** 6)
    monkeypatch.setattr(ops, "corner_tables", lambda *a: seen.append(a) or real(*a))
    p = reference.make_params(ref_cfg(), 5)
    fns = nerfacto.field_fns(p, SMOKE.model)
    (tables, g, memory), = seen
    assert g == (SMOKE.model.grid,) + SMOKE.model.proposal_grids and memory == 240 * 10 ** 6
    for key, levels in (("corners", hashgrid.field_corner_levels(g, memory)),
                        ("xrows", hashgrid.field_xrow_levels(g, memory))):
        got = [len(c) for c in [fns.bound.params[key]]
               + [q[key] for q in fns.bound.params["proposals"]]]
        assert tuple(got) == tuple(map(len, levels))


def test_proposal_executable_takes_tables_as_arguments(field):
    """Stage A is one executable of its own name, over the bound params;
    a jitted rebind derives no corner rows."""
    cam = scene.look_at_camera(8, 8, 0.3, 0.4)
    fkey, bound = fields.unbind(field)
    lowered = pipeline.proposal_edges.lower(
        fkey, 64, (8, 8, cam.focal), bound, jnp.asarray(cam.c2w_rot), jnp.asarray(cam.origin))
    assert "proposal_edges" in lowered.as_text().splitlines()[0]
    e = pipeline.proposal_phase(field, SMOKE.asdr, cam)
    assert e.shape == (64, SMOKE.model.samples[-1] + 1)
    np.testing.assert_allclose(e, field.sampler.edges(*scene.camera_rays(cam)), atol=2e-6)


def test_harness_runs_a_tiny_nerfacto_cell_correct(tmp_path):
    """``harness.run`` on a nerfacto cell at SMOKE size on 16x16 fresh
    views, holding the new cell's limits, is correct; the traced run
    reads the proposal rows a ray."""
    from bench import harness
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    b = root / "bench"
    (b / "configs" / "nerfacto-smoke.json").write_text(json.dumps(ref_cfg()))
    mix = json.loads((b / "traffic" / "fresh-128.json").read_text())
    mix.update(frame=[16, 16], check_pixels_per_frame=64)
    (b / "traffic" / "fresh-tiny.json").write_text(json.dumps(mix))
    src, cell = "nerfacto.fresh-128", "nerfacto-smoke.fresh"
    shutil.copy(b / "limits" / f"{src}.json", b / "limits" / f"{cell}.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "nerfacto-smoke", "source": "https://arxiv.org/abs/2302.04264",
                            "file": "bench/configs/nerfacto-smoke.json", "reduced": [],
                            "why": "CPU"})
    spec["workloads"].append({"name": cell, "config": "nerfacto-smoke",
                              "traffic": "fresh-tiny", "chips": 1, "why": "CPU"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if src in m.get("workloads", []):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for trace in (False, True):
        r = harness.run(cell, SEED, 1.0, trace, time.perf_counter(),
                        require_chip=False, root=root)
        assert r["correct"] and r["failed"] == 0, r["checks"]
        if trace:
            assert r["metrics"]["proposal_rows_per_ray.nerfacto"]["value"] == 88 * 17
        else:
            assert {"rays_per_s", "setup_s"} <= set(r["metrics"])
