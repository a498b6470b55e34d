"""Two-phase ASDR pipeline on the exact analytic field."""
import jax.numpy as jnp
import pytest

from repro.core import fields, pipeline, rendering, scene


@pytest.fixture(scope="module")
def setup():
    field = scene.make_scene("mic")
    fns = fields.analytic_field_fns(field)
    cam = scene.look_at_camera(24, 24, theta=0.7, phi=0.5)
    o, d = scene.camera_rays(cam)
    full, _ = pipeline.render_fixed_fns(fns, o, d, 96)
    return field, fns, cam, o, d, full


def test_asdr_near_lossless_with_fewer_samples(setup):
    field, fns, cam, o, d, full = setup
    acfg = pipeline.ASDRConfig(
        ns_full=96, probe_stride=4, block_size=96, chunk=16,
        candidates=(12, 24, 48), delta=1.0 / 2048.0,
    )
    img, stats = pipeline.render_asdr_image(fns, acfg, cam)
    p = float(rendering.psnr(img, full.reshape(24, 24, 3)))
    assert p > 35.0                       # near-lossless vs fixed-96
    assert stats["avg_samples_per_ray"] < 96   # fewer samples used
    assert stats["phase2_fraction_of_baseline"] < 0.8


def test_background_gets_fewest_samples(setup):
    """mic scene is background-heavy — paper: ~40% of pixels can drop to
    the minimum count."""
    field, fns, cam, o, d, full = setup
    acfg = pipeline.ASDRConfig(
        ns_full=96, probe_stride=4, block_size=96, chunk=16,
        candidates=(12, 24, 48),
    )
    counts, _ = pipeline.probe_phase(fns, acfg, cam)
    frac_min = float(jnp.mean(counts == 12))
    assert frac_min > 0.3


@pytest.mark.slow
def test_early_termination_reduces_chunks(setup):
    field, fns, cam, o, d, full = setup
    kw = dict(ns_full=96, probe_stride=4, block_size=96, chunk=16,
              candidates=(12, 24, 48))
    on = pipeline.ASDRConfig(early_termination=True, **kw)
    off = pipeline.ASDRConfig(early_termination=False, **kw)
    _, s_on = pipeline.render_asdr_image(fns, on, cam)
    _, s_off = pipeline.render_asdr_image(fns, off, cam)
    assert float(s_on["samples_processed"]) <= float(s_off["samples_processed"])
    # ET must not change the image materially (paper §6.6: lossless)
    img_on, _ = pipeline.render_asdr_image(fns, on, cam)
    img_off, _ = pipeline.render_asdr_image(fns, off, cam)
    assert float(rendering.psnr(img_on, img_off)) > 45.0


def test_block_unsort_roundtrip(setup):
    """render_adaptive must return rays in the original order."""
    field, fns, cam, o, d, full = setup
    R = o.shape[0]
    counts = jnp.full((R,), 24, jnp.int32)
    acfg = pipeline.ASDRConfig(ns_full=96, block_size=96, chunk=8,
                               group=1, early_termination=False)
    rgb, acc, _ = pipeline.render_adaptive(fns, acfg, o, d, counts)
    ref, _ = pipeline.render_fixed_fns(fns, o, d, 24)
    # same per-ray sample count, same order -> close colors per ray
    err = float(jnp.max(jnp.abs(rgb - ref)))
    assert err < 1e-3  # same sampling grid, same order


def _unblocked_probe(fns, acfg, cam, key):
    """Phase I as one render_fixed_fns call over every probe ray."""
    from repro.core import adaptive
    H, W = cam.height, cam.width
    o, d = scene.camera_rays(cam)
    jj, ii = jnp.meshgrid(jnp.arange(0, H, acfg.probe_stride),
                          jnp.arange(0, W, acfg.probe_stride), indexing="ij")
    idx = (jj * W + ii).reshape(-1)
    hw = (jj.shape[0], jj.shape[1])
    rgb, aux = pipeline.render_fixed_fns(fns, o[idx], d[idx], acfg.ns_full,
                                         key)
    pc = adaptive.probe_counts(aux["sigmas"], aux["colors"], rgb,
                               acfg.ns_full, acfg.candidates, acfg.delta)
    t = rendering.expected_termination_depth(aux["weights"], aux["ts"],
                                             aux["acc"], scene.FAR)
    return (adaptive.interpolate_counts(pc, hw, (H, W), acfg.candidates,
                                        acfg.ns_full),
            adaptive.interpolate_map(aux["acc"], hw, (H, W)),
            adaptive.interpolate_map(t, hw, (H, W)))


@pytest.mark.parametrize("keyed", [False, True])
def test_blocked_probe_equals_unblocked(keyed):
    """The jitted, ray-blocked probe gives the one-call probe's maps at
    SMOKE size (blocks do not divide the probe rays: pads are cropped)."""
    import jax
    import numpy as np
    from repro.configs.ingp_asdr import SMOKE
    from repro.core import model as model_lib
    params = model_lib.init_ngp(jax.random.PRNGKey(0), SMOKE.model)
    params = dict(params, grid=params["grid"] * 1e3)
    fns = model_lib.field_fns(params, SMOKE.model)
    cam = scene.look_at_camera(44, 44, theta=0.6, phi=0.5)  # 11x11 probes
    key = jax.random.PRNGKey(3) if keyed else None
    counts, cost, opacity, depth = pipeline.probe_phase(
        fns, SMOKE.asdr, cam, key, return_opacity=True, return_depth=True)
    want = _unblocked_probe(fns, SMOKE.asdr, cam, key)
    rays_per_block = (SMOKE.asdr.block_size * SMOKE.asdr.chunk
                      // SMOKE.asdr.ns_full)
    assert 121 % rays_per_block and cost == 121 * SMOKE.asdr.ns_full
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(opacity), np.asarray(want[1]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(depth), np.asarray(want[2]),
                               rtol=0, atol=1e-6)
