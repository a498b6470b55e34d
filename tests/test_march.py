"""The chunked Phase-II march (core/pipeline.march_blocks).

Two kinds of check:

* the served Instant-NGP field (``ops.field_fns``: the XLA gather encode
  feeding the Pallas MLP kernels) marched against the pure-jnp model
  field under ``ref.ref_march`` — rgb/acc/depth close, and ``chunks_done``
  and the per-ray chunk counts EXACTLY equal: early termination is part
  of the march's contract, not a tolerance;
* the served march (``pool.batched_march``) of an ingp and a TensoRF
  field against a one-pass oracle that shares none of the march's
  compositing: sigma at every sample, anchor colors expanded within each
  chunk, and ``rendering.composite``'s cumulative transmittance over the
  whole ray instead of a log-transmittance carried from chunk to chunk.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.tensorf_vm192 import SMOKE
from repro.core import decouple, pipeline, rendering, scene, tensorf
from repro.core.model import NGPConfig, init_ngp
from repro.core.model import field_fns as jnp_field_fns
from repro.kernels import ops, ref
from repro.serve import pool


@pytest.fixture(scope="module")
def model():
    cfg = NGPConfig.small()
    params = init_ngp(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def both_fns(model):
    """(kernel-backed FieldFns, pure-jnp FieldFns) over the same params."""
    cfg, params = model
    return ops.field_fns(params, cfg), jnp_field_fns(params, cfg)


def _blocked_rays(n_blocks, block_size, theta=0.6, phi=0.4):
    cam = scene.look_at_camera(n_blocks * block_size // 8, 8,
                               theta=theta, phi=phi)
    o, d = scene.camera_rays(cam)
    return (o.reshape(n_blocks, block_size, 3),
            d.reshape(n_blocks, block_size, 3))


def _acfg(**kw):
    base = dict(block_size=32, chunk=16, group=2)
    base.update(kw)
    return pipeline.ASDRConfig(**base)


def _assert_march_equal(got, want, atol=1e-5):
    """(rgb, acc, depth, chunks, ray_chunks) parity; the two chunk
    counters are exactly equal."""
    for g, w, name in [(got[0], want[0], "rgb"), (got[1], want[1], "acc"),
                       (got[2], want[2], "depth")]:
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=atol, err_msg=name)
    assert np.array_equal(np.asarray(got[3]), np.asarray(want[3])), (
        f"chunks_done mismatch: {got[3]} vs {want[3]}")
    assert np.array_equal(np.asarray(got[4]), np.asarray(want[4])), (
        "per-ray chunks mismatch")


# ------------------------------------- kernel field vs the pure-jnp field
def test_kernel_march_matches_reference(both_fns):
    """Budgets cover budget = chunk (16), multi-chunk (48), and a budget
    not divisible by chunk (33 -> 3 chunks, last partially masked)."""
    fns_k, fns_j = both_fns
    acfg = _acfg()
    o_b, d_b = _blocked_rays(3, acfg.block_size)
    budgets = jnp.asarray([16, 48, 33], jnp.int32)
    got = pipeline.march_blocks(fns_k, acfg, o_b, d_b, budgets)
    want = ref.ref_march(fns_j, acfg, o_b, d_b, budgets)
    _assert_march_equal(got, want)
    assert np.asarray(got[3]).tolist() == [1, 3, 3]


def test_kernel_march_budget_below_chunk(both_fns):
    fns_k, fns_j = both_fns
    acfg = _acfg()
    o_b, d_b = _blocked_rays(1, acfg.block_size)
    budgets = jnp.asarray([7], jnp.int32)
    got = pipeline.march_blocks(fns_k, acfg, o_b, d_b, budgets)
    want = ref.ref_march(fns_j, acfg, o_b, d_b, budgets)
    _assert_march_equal(got, want)
    assert int(got[3][0]) == 1


def test_kernel_march_group_not_dividing_chunk(both_fns):
    """group=3 with chunk=16: the last anchor covers a short tail and the
    lerp's right neighbour clamps (decouple.interpolate_group_colors)."""
    fns_k, fns_j = both_fns
    acfg = _acfg(group=3)
    o_b, d_b = _blocked_rays(2, acfg.block_size)
    budgets = jnp.asarray([32, 21], jnp.int32)
    got = pipeline.march_blocks(fns_k, acfg, o_b, d_b, budgets)
    want = ref.ref_march(fns_j, acfg, o_b, d_b, budgets)
    _assert_march_equal(got, want)


def test_kernel_march_early_termination_saturated_block(model):
    """A block whose rays ALL saturate early stops the while_loop at the
    same chunk on both fields (chunks_done < ceil(budget/chunk))."""
    cfg, params = model
    # saturate the field: non-negative features + amplified non-negative
    # density weights drive sigma to trunc_exp's clip everywhere inside
    # the cube, so transmittance collapses within the first occupied chunk
    hot = dict(params)
    hot["grid"] = jnp.abs(params["grid"]) + 0.5
    hot["mlps"] = dict(params["mlps"])
    hot["mlps"]["density"] = [jnp.abs(w) * 4.0
                              for w in params["mlps"]["density"]]
    fns_k = ops.field_fns(hot, cfg)
    fns_j = jnp_field_fns(hot, cfg)
    acfg = _acfg(block_size=8)
    # rays enter the cube at t = 0.3 (sample index ~9 of 192): saturation
    # is guaranteed inside chunk 0, termination by the next chunk check
    o = jnp.tile(jnp.asarray([0.45, 0.45, -0.3]), (8, 1))
    o = o + jnp.linspace(0.0, 0.1, 8)[:, None] * jnp.asarray([1.0, 1.0, 0.0])
    d = jnp.tile(jnp.asarray([0.0, 0.0, 1.0]), (8, 1))
    o_b, d_b = o[None], d[None]
    budgets = jnp.asarray([192], jnp.int32)
    got = pipeline.march_blocks(fns_k, acfg, o_b, d_b, budgets)
    want = ref.ref_march(fns_j, acfg, o_b, d_b, budgets)
    _assert_march_equal(got, want)
    assert int(got[3][0]) < 192 // acfg.chunk, "early termination never fired"
    np.testing.assert_allclose(np.asarray(got[1]), 1.0, atol=1e-4)


def test_kernel_march_early_termination_off(both_fns):
    """With early_termination=False the loop runs every chunk."""
    fns_k, fns_j = both_fns
    acfg = _acfg(early_termination=False)
    o_b, d_b = _blocked_rays(1, acfg.block_size)
    budgets = jnp.asarray([48], jnp.int32)
    got = pipeline.march_blocks(fns_k, acfg, o_b, d_b, budgets)
    want = ref.ref_march(fns_j, acfg, o_b, d_b, budgets)
    _assert_march_equal(got, want)
    assert int(got[3][0]) == 3


def test_kernel_march_pad_blocks(both_fns):
    """Serve-layer pad blocks: budget=1, straight-up rays that never enter
    the cube, background output."""
    fns_k, fns_j = both_fns
    acfg = _acfg(block_size=8)
    o = jnp.zeros((1, 8, 3), jnp.float32)
    d = jnp.tile(jnp.asarray([0.0, 0.0, -1.0]), (1, 8, 1))
    budgets = jnp.asarray([1], jnp.int32)
    got = pipeline.march_blocks(fns_k, acfg, o, d, budgets)
    want = ref.ref_march(fns_j, acfg, o, d, budgets)
    _assert_march_equal(got, want)
    np.testing.assert_allclose(np.asarray(got[1]), 0.0, atol=1e-6)  # acc
    np.testing.assert_allclose(np.asarray(got[0]), 1.0, atol=1e-6)  # white


def test_kernel_march_density_only(both_fns):
    """Density-only marches (serve's warp refresh path) skip the color
    chain entirely; acc/depth/chunks still match the reference."""
    fns_k, fns_j = both_fns
    acfg = _acfg()
    o_b, d_b = _blocked_rays(2, acfg.block_size)
    budgets = jnp.asarray([48, 33], jnp.int32)
    got = pipeline.march_blocks(fns_k, acfg, o_b, d_b, budgets,
                                density_only=True)
    want = ref.ref_march(fns_j, acfg, o_b, d_b, budgets, density_only=True)
    for g, w, name in [(got[1], want[1], "acc"), (got[2], want[2], "depth")]:
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    assert np.array_equal(np.asarray(got[3]), np.asarray(want[3]))
    # and density-only vs full march agree on acc/depth too
    full = pipeline.march_blocks(fns_k, acfg, o_b, d_b, budgets)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(full[1]),
                               rtol=1e-4, atol=1e-5)


# --------------------------------------- served march vs a one-pass oracle
def _one_pass(fns, acfg, o, d, budget):
    """One block of rays o/d (B, 3) rendered in one pass at the march's
    own samples ``t_i = NEAR + (i + 1/2) (FAR - NEAR) / budget``: sigma at
    every sample, colors at each chunk's anchors expanded within the chunk
    (anchors past the budget in a cut-short last chunk still feed the
    lerp, as in the march), composited by ``rendering.composite``.
    Returns (rgb, acc, depth, ray_chunks) with ray_chunks the chunks each
    ray starts with transmittance above the termination threshold."""
    B, C, g = o.shape[0], acfg.chunk, acfg.group
    n_chunks = -(-budget // C)
    dt = (scene.FAR - scene.NEAR) / budget
    ts = jnp.asarray(scene.NEAR + (np.arange(n_chunks * C) + 0.5) * dt,
                     jnp.float32)
    pts = o[:, None, :] + ts[None, :, None] * d[:, None, :]
    sigma, _ = fns.density(pts[:, :budget].reshape(-1, 3))
    sigma = sigma.reshape(B, budget)
    anchors = pts.reshape(B, n_chunks, C, 3)[:, :, ::g]
    A = anchors.shape[2]
    _, geo = fns.density(anchors.reshape(-1, 3))
    col = fns.color(geo, jnp.repeat(d, n_chunks * A, axis=0))
    colors = decouple.interpolate_group_colors(
        col.reshape(B * n_chunks, A, 3), g, C)
    colors = colors.reshape(B, n_chunks * C, 3)[:, :budget]
    deltas = jnp.full((B, budget), dt, jnp.float32)
    rgb, acc, w = rendering.composite(sigma, colors, deltas)
    depth = rendering.expected_termination_depth(w, ts[None, :budget], acc,
                                                 scene.FAR)
    trans = rendering.transmittance(rendering.alphas_from_sigmas(sigma,
                                                                 deltas))
    live = trans[:, ::C] > rendering.EARLY_TERM_TRANSMITTANCE
    return rgb, acc, depth, live.sum(axis=1)


@pytest.fixture(scope="module")
def families(model):
    """The two field families the cells serve, at test size: the ingp
    kernel field and TensoRF's VM field (SMOKE widths, tables scaled so
    the views are partly opaque)."""
    cfg, params = model
    tp = tensorf.init_params(jax.random.PRNGKey(3), SMOKE.model, scale=1.6)
    return {"ingp": ops.field_fns(params, cfg),
            "tensorf": tensorf.field_fns(tp, SMOKE.model)}


@pytest.mark.parametrize("family", ["ingp", "tensorf"])
@pytest.mark.parametrize("budget,chunk,group", [
    (32, 16, 2),
    (12, 16, 2),    # budget under one chunk
    (40, 16, 2),    # cut-short last chunk
    (48, 16, 3),    # group does not divide the chunk
    (32, 8, 1),     # no decoupling
])
def test_march_matches_one_pass_oracle(families, family, budget, chunk,
                                       group):
    fns = families[family]
    acfg = _acfg(block_size=16, chunk=chunk, group=group,
                 early_termination=False)
    o_b, d_b = _blocked_rays(1, acfg.block_size)
    rgb, acc, depth, chunks, ray_chunks = pool.batched_march(fns, acfg)(
        o_b, d_b, jnp.asarray([budget], jnp.int32))
    w_rgb, w_acc, w_depth, w_live = _one_pass(fns, acfg, o_b[0], d_b[0],
                                              budget)
    assert float(w_acc.max()) > 0.1, "the view must not be empty"
    for g, w, name in [(rgb[0], w_rgb, "rgb"), (acc[0], w_acc, "acc"),
                       (depth[0], w_depth, "depth")]:
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    assert int(chunks[0]) == -(-budget // chunk)
    assert np.array_equal(np.asarray(ray_chunks[0]), np.asarray(w_live))


# --------------------------------------------------- weight-pack memoization
def test_weight_packing_memoized(model):
    cfg, params = model
    ops.packed_weights(params["mlps"], cfg.net)   # warm (may hit or miss)
    s1 = ops.pack_cache_stats()
    wd1, wc1 = ops.packed_weights(params["mlps"], cfg.net)
    s2 = ops.pack_cache_stats()
    assert s2["hits"] == s1["hits"] + 1 and s2["misses"] == s1["misses"]
    # same objects back (memoized, not re-traced)
    wd2, wc2 = ops.packed_weights(params["mlps"], cfg.net)
    assert wd2 is wd1 and wc2 is wc1
    # distinct params are a distinct entry
    other = init_ngp(jax.random.PRNGKey(1), cfg)
    ops.packed_weights(other["mlps"], cfg.net)
    s3 = ops.pack_cache_stats()
    assert s3["misses"] == s2["misses"] + 1
    assert s3["size"] >= 2


def test_field_fns_share_packed_weights(model):
    """Two fields built over the same (fresh) params pack their weights
    once: the first density call misses, the second hits."""
    cfg, _ = model
    params = init_ngp(jax.random.PRNGKey(2), cfg)
    pts = jnp.full((4, 3), 0.5)
    s1 = ops.pack_cache_stats()
    ops.field_fns(params, cfg).density(pts)
    ops.field_fns(params, cfg).density(pts)
    s2 = ops.pack_cache_stats()
    assert s2["misses"] - s1["misses"] == 1
    assert s2["hits"] > s1["hits"]
