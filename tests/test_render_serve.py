"""Render serving engine + cross-frame probe reuse invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import adaptive, fields, pipeline, scene
from repro.serve.render_engine import (RenderRequest, RenderServeConfig,
                                       RenderServingEngine)


ACFG = pipeline.ASDRConfig(ns_full=48, probe_stride=4, candidates=(8, 16, 32),
                           block_size=64, chunk=16, sort_by_opacity=False)


@pytest.fixture(scope="module")
def setup():
    flds = {"mic": fields.analytic_field_fns(scene.make_scene("mic")),
            "hotdog": fields.analytic_field_fns(scene.make_scene("hotdog"))}
    cam = scene.look_at_camera(16, 16, theta=0.7, phi=0.5)
    return flds, cam


def test_engine_matches_single_image_pipeline(setup):
    """Pooled multi-request serving must be bit-identical to rendering each
    view alone through render_asdr_image (fresh probes, stable sort)."""
    flds, cam = setup
    eng = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=2, blocks_per_batch=4, reuse=None))
    reqs = [RenderRequest(rid=0, scene="mic", cam=cam),
            RenderRequest(rid=1, scene="hotdog", cam=cam)]
    done = {r.rid: r for r in eng.render(reqs)}
    for rid, sc in [(0, "mic"), (1, "hotdog")]:
        ref, _ = pipeline.render_asdr_image(flds[sc], ACFG, cam)
        np.testing.assert_array_equal(done[rid].image, np.asarray(ref))


def test_probe_reuse_zero_distance_is_identical(setup):
    """At zero pose distance the reuse path must equal re-probing exactly:
    same count map (dilation radius 0), same rendered image."""
    flds, cam = setup
    fns = flds["mic"]
    cache = pipeline.ProbeCache(pipeline.ProbeReuseConfig())
    c1, cost1, o1, r1 = pipeline.probe_phase_cached(fns, ACFG, cam, cache)
    # a newly constructed but identical camera
    cam_b = scene.look_at_camera(16, 16, theta=0.7, phi=0.5)
    c2, cost2, o2, r2 = pipeline.probe_phase_cached(fns, ACFG, cam_b, cache)
    assert (not r1) and r2 and cost1 > 0 and cost2 == 0
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    fresh, _, _ = pipeline.probe_phase(fns, ACFG, cam_b, return_opacity=True)
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(fresh))


def test_engine_reuse_frames_identical_on_replay(setup):
    """Serving the same trajectory twice: lap-2 frames reuse lap-1 probes
    and must render bit-identically to an always-probe engine."""
    flds, _ = setup
    def traj():
        return [RenderRequest(rid=i, scene="mic",
                              cam=scene.look_at_camera(
                                  16, 16, theta=0.7 + 0.1 * (i % 2), phi=0.5))
                for i in range(4)]
    reuse = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=2, blocks_per_batch=4,
        reuse=pipeline.ProbeReuseConfig(max_angle_deg=1.0,
                                        max_translation=0.02)))
    probe = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=2, blocks_per_batch=4, reuse=None))
    dr = {r.rid: r for r in reuse.render(traj())}
    dp = {r.rid: r for r in probe.render(traj())}
    assert dr[2].stats["probe_reused"] and dr[3].stats["probe_reused"]
    assert reuse.engine_stats()["reused_probe_fraction"] == 0.5
    for rid in dr:
        np.testing.assert_array_equal(dr[rid].image, dp[rid].image)


def test_probe_cache_refresh_every_k(setup):
    flds, cam = setup
    fns = flds["mic"]
    cache = pipeline.ProbeCache(pipeline.ProbeReuseConfig(refresh_every=2))
    pipeline.probe_phase_cached(fns, ACFG, cam, cache)      # miss
    for i in range(2):                                       # 2 hits
        *_ , reused = pipeline.probe_phase_cached(fns, ACFG, cam, cache)
        assert reused
    *_, reused = pipeline.probe_phase_cached(fns, ACFG, cam, cache)
    assert not reused                                        # forced refresh
    assert cache.refreshes == 1 and cache.hits == 2


def test_padding_rays_do_not_leak(setup):
    """Image rows must be independent of the pad rays' content."""
    flds, cam = setup
    fns = flds["mic"]
    o, d = scene.camera_rays(cam)                 # R = 256, block 96 -> pad
    acfg = pipeline.ASDRConfig(ns_full=48, candidates=(8, 16, 32),
                               block_size=96, chunk=16)
    R = o.shape[0]
    counts = jnp.asarray(np.random.default_rng(0).choice(
        [8, 16, 32], size=(R,)), jnp.int32)
    op, dp_, cp, _, pad = pipeline.pad_rays_to_blocks(acfg, o, d, counts)
    assert pad == (-R) % 96 and pad > 0
    rgb_a, _, _ = pipeline.render_adaptive(fns, acfg, op, dp_, cp)
    # replace pad rays with rays that stare straight into the scene
    op2 = op.at[R:].set(jnp.asarray([0.5, 0.5, -0.5]))
    dp2 = dp_.at[R:].set(jnp.asarray([0.0, 0.0, 1.0]))
    rgb_b, _, _ = pipeline.render_adaptive(fns, acfg, op2, dp2, cp)
    np.testing.assert_array_equal(np.asarray(rgb_a[:R]),
                                  np.asarray(rgb_b[:R]))


@pytest.mark.parametrize("by_opacity", [False, True])
def test_block_sort_is_permutation_inverse(by_opacity):
    """block_sort order must be an exact permutation; the unsort used by
    render_adaptive must be its exact inverse."""
    rng = np.random.default_rng(1)
    R = 512
    acfg = pipeline.ASDRConfig(candidates=(8, 16, 32), block_size=64,
                               sort_by_opacity=by_opacity)
    counts = jnp.asarray(rng.choice([8, 16, 32, 96], size=(R,)), jnp.int32)
    opacity = jnp.asarray(rng.uniform(size=(R,)), jnp.float32)
    order, budgets = pipeline.block_sort(acfg, counts, opacity)
    order_np = np.asarray(order)
    assert sorted(order_np.tolist()) == list(range(R))     # permutation
    inv = np.zeros(R, np.int64)
    inv[order_np] = np.arange(R)
    np.testing.assert_array_equal(order_np[inv], np.arange(R))
    np.testing.assert_array_equal(inv[order_np], np.arange(R))
    # budgets conservative: every ray's count <= its block budget
    sorted_counts = np.asarray(counts)[order_np].reshape(-1, 64)
    assert (sorted_counts.max(axis=1) == np.asarray(budgets)).all()


def test_pose_distance_and_dilation_radius():
    cam_a = scene.look_at_camera(16, 16, theta=0.7, phi=0.5)
    cam_b = scene.look_at_camera(16, 16, theta=0.75, phi=0.5)
    ang, tr = adaptive.pose_distance(cam_a, cam_a)
    assert ang == 0.0 and tr == 0.0
    ang_ab, tr_ab = adaptive.pose_distance(cam_a, cam_b)
    assert ang_ab > 0.0 and tr_ab > 0.0
    assert adaptive.reuse_dilation_radius(cam_a, 0.0, 0.0, scene.NEAR) == 0
    r_small = adaptive.reuse_dilation_radius(cam_a, 1e-4, 0.0, scene.NEAR)
    assert r_small == 0                      # sub-half-pixel noise
    r_big = adaptive.reuse_dilation_radius(cam_a, ang_ab, tr_ab, scene.NEAR)
    assert r_big >= 1
    # wide-FOV camera: the corner term must grow the bound, never shrink it
    wide = scene.look_at_camera(16, 16, theta=0.7, phi=0.5, fov_deg=90.0)
    assert (adaptive.reuse_dilation_radius(wide, 0.05, 0.0, scene.NEAR)
            >= adaptive.reuse_dilation_radius(cam_a, 0.05, 0.0, scene.NEAR))
    # an in-plane roll keeps the view direction but permutes every pixel:
    # the full-rotation metric must see it as a large distance
    rolled = scene.Camera(
        cam_a.height, cam_a.width, cam_a.focal,
        np.stack([cam_a.c2w_rot[:, 1], -cam_a.c2w_rot[:, 0],
                  cam_a.c2w_rot[:, 2]], axis=-1),
        cam_a.origin)
    ang_roll, tr_roll = adaptive.pose_distance(cam_a, rolled)
    assert ang_roll > np.deg2rad(45) and tr_roll == 0.0


def test_probe_cache_rejects_different_focal(setup):
    """Same pose, different zoom: every ray differs — must re-probe."""
    flds, cam = setup
    fns = flds["mic"]
    cache = pipeline.ProbeCache(pipeline.ProbeReuseConfig())
    pipeline.probe_phase_cached(fns, ACFG, cam, cache)
    zoomed = scene.Camera(cam.height, cam.width, cam.focal * 1.5,
                          cam.c2w_rot, cam.origin)
    *_, reused = pipeline.probe_phase_cached(fns, ACFG, zoomed, cache)
    assert not reused


def test_dilate_count_map_is_conservative():
    counts = jnp.asarray(np.random.default_rng(2).choice(
        [8, 16, 32], size=(64,)), jnp.int32)
    out = adaptive.dilate_count_map(counts, (8, 8), 1)
    assert (np.asarray(out) >= np.asarray(counts)).all()    # max filter
    np.testing.assert_array_equal(
        np.asarray(adaptive.dilate_count_map(counts, (8, 8), 0)),
        np.asarray(counts))
    # a uniform map is a fixed point at any radius
    uni = jnp.full((64,), 16, jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(adaptive.dilate_count_map(uni, (8, 8), 2)), np.asarray(uni))
    # border_fill covers content entering from off-screen: the radius-wide
    # band rises to at least the fill, the interior is untouched
    bf = np.asarray(adaptive.dilate_count_map(uni, (8, 8), 1,
                                              border_fill=96)).reshape(8, 8)
    assert (bf[0] == 96).all() and (bf[-1] == 96).all()
    assert (bf[:, 0] == 96).all() and (bf[:, -1] == 96).all()
    assert (bf[1:-1, 1:-1] == 16).all()


def test_probe_cache_rejects_different_acfg(setup):
    """Count maps are acfg-specific: a changed delta/candidates must not
    serve the stale maps."""
    flds, cam = setup
    fns = flds["mic"]
    cache = pipeline.ProbeCache(pipeline.ProbeReuseConfig())
    pipeline.probe_phase_cached(fns, ACFG, cam, cache)
    import dataclasses
    loose = dataclasses.replace(ACFG, delta=0.1)
    *_, reused = pipeline.probe_phase_cached(fns, loose, cam, cache)
    assert not reused
    # same acfg still hits
    *_, reused = pipeline.probe_phase_cached(fns, loose, cam, cache)
    assert reused


def test_streaming_dispatch_bit_identical(setup):
    """inflight_batches > 1 changes only WHEN batches launch, never what
    they compute: frames and deterministic counters must match the
    one-batch-per-round engine exactly, while the streaming engine's
    rounds actually carry multiple batches."""
    from repro.serve import stats as stats_lib
    flds, cam = setup
    reqs = lambda: [RenderRequest(rid=i, scene=s, cam=cam)
                    for i, s in enumerate(["mic", "hotdog", "mic",
                                           "hotdog"])]
    mk = lambda n: RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=4, blocks_per_batch=2, reuse=None, inflight_batches=n))
    one, many = mk(1), mk(3)
    d1 = {r.rid: r for r in one.render(reqs())}
    dn = {r.rid: r for r in many.render(reqs())}
    for rid in d1:
        np.testing.assert_array_equal(d1[rid].image, dn[rid].image)
    s1, sn = one.engine_stats(), many.engine_stats()
    for k in stats_lib.DETERMINISTIC_COUNTERS:
        assert s1[k] == sn[k], k
    # the streaming engine really ran multi-batch rounds
    assert max(sn["batches_per_round"]) > 1
    assert max(s1["batches_per_round"]) == 1


def test_march_round_observability(setup):
    """engine_stats() must expose the round ledger: wall-time percentiles
    and a batches-per-round histogram whose mass equals the batch count."""
    flds, cam = setup
    eng = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=2, blocks_per_batch=4, reuse=None, inflight_batches=2))
    eng.render([RenderRequest(rid=0, scene="mic", cam=cam),
                RenderRequest(rid=1, scene="hotdog", cam=cam)])
    st = eng.engine_stats()
    assert st["march_rounds"] > 0
    assert st["march_ms_p50"] > 0.0 and st["march_ms_p99"] > 0.0
    hist = st["batches_per_round"]
    assert hist and sum(k * v for k, v in hist.items()) == st["batches"]
    assert sum(hist.values()) == st["march_rounds"]


def test_engine_stats_expose_pack_cache(setup):
    """engine_stats() surfaces the kernels weight-pack memoization
    ledger (a process-wide LRU) and tracks its hit/miss accounting."""
    from repro.core.model import NGPConfig, init_ngp
    from repro.kernels import ops
    import jax
    flds, cam = setup
    eng = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=1, blocks_per_batch=2, reuse=None))
    st0 = eng.engine_stats()
    for k in ("pack_cache_hits", "pack_cache_misses", "pack_cache_size"):
        assert k in st0, k
    direct = ops.pack_cache_stats()
    assert st0["pack_cache_hits"] == direct["hits"]
    assert st0["pack_cache_misses"] == direct["misses"]
    # a fresh params dict is a miss, re-packing it is a hit — both must
    # show up in the engine's ledger exactly
    cfg = NGPConfig.small()
    params = init_ngp(jax.random.PRNGKey(42), cfg)
    ops.packed_weights(params["mlps"], cfg.net)
    ops.packed_weights(params["mlps"], cfg.net)
    st1 = eng.engine_stats()
    assert st1["pack_cache_misses"] == st0["pack_cache_misses"] + 1
    assert st1["pack_cache_hits"] == st0["pack_cache_hits"] + 1
    assert st1["pack_cache_size"] >= 1


def test_ray_exit_skip_counter(setup):
    """pool.collect prices per-ray early exit: with the flag on, the
    gap between each block's chunk count and its rays' live-chunk
    counts lands in ``ray_exit_samples_skipped`` (chunk samples per
    skipped ray-chunk); with the flag off the counter stays zero."""
    import dataclasses
    from repro.serve import pool as pool_lib, stats as stats_lib

    class _FakeReq:
        rid, scene = 0, "mic"

    class _FakeSlot:
        req = _FakeReq()

        def deliver(self, bi, rgb, acc, depth, chunks, cached=False):
            pass

    B = 4
    acfg = dataclasses.replace(ACFG, block_size=B, per_ray_early_exit=True)
    counters = stats_lib.EngineCounters()
    pool = pool_lib.BlockPool(acfg, 2, None, counters)
    slot = _FakeSlot()
    batch = [(slot, 0, None, None, 64, None, None, False)]
    out = (np.zeros((2, B, 3)), np.zeros((2, B)), np.zeros((2, B)),
           np.asarray([4, 1]),                      # block chunks (1 pad)
           np.asarray([[4, 2, 1, 4], [1, 1, 1, 1]]))  # per-ray chunks
    pool.collect((batch, [], 1, out, 1))
    # real block: (4-4)+(4-2)+(4-1)+(4-4) = 5 skipped ray-chunks; the
    # pad block's gap must NOT count
    assert counters.ray_exit_samples_skipped == 5 * acfg.chunk
    # flag off: identical collect books nothing
    counters2 = stats_lib.EngineCounters()
    pool2 = pool_lib.BlockPool(ACFG, 2, None, counters2)
    pool2.collect((batch, [], 1, out, 2))
    assert counters2.ray_exit_samples_skipped == 0
    assert "ray_exit_samples_skipped" in stats_lib.engine_stats(
        counters, {}, {}, None)


def test_density_refresh_enables_radiance_chaining(setup):
    """Opt-in density refresh: partially-warped frames re-march their
    warp-valid rays color-free, recovering marched acc/depth — so they
    enter the radiance cache and later frames can warp FROM them.
    Without it, warps never chain (each hit must reach a fully-marched
    frame) and the later hits become misses."""
    flds, _ = setup
    from repro.serve.render_engine import RadianceReuseConfig
    def traj():
        return [RenderRequest(rid=i, scene="mic",
                              cam=scene.look_at_camera(
                                  32, 32, theta=0.7 + 0.025 * i, phi=0.5))
                for i in range(4)]
    mk = lambda refresh: RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=1, blocks_per_batch=4, prefetch=0,
        reuse=pipeline.ProbeReuseConfig(),
        radiance=RadianceReuseConfig(),
        density_refresh=refresh))
    base, refr = mk(False), mk(True)
    db = {r.rid: r for r in base.render(traj())}
    dr = {r.rid: r for r in refr.render(traj())}
    sb, sr = base.engine_stats(), refr.engine_stats()
    # chaining: the refreshed engine converts later misses into hits
    assert sr["radiance_hits"] > sb["radiance_hits"]
    assert any(r.stats.get("density_rays", 0) > 0 for r in dr.values())
    # refreshed frames march FEWER color rays overall, not more quality
    # loss: every frame stays close to the never-reuse render
    full = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=1, blocks_per_batch=4, reuse=None))
    df = {r.rid: r for r in full.render(traj())}
    from repro.core import rendering
    for rid in dr:
        p = float(rendering.psnr(jnp.asarray(dr[rid].image),
                                 jnp.asarray(df[rid].image)))
        assert p > 30.0, (rid, p)
