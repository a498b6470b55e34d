"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashgrid, mlp as mlp_lib
from repro.core.model import NGPConfig, init_ngp
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def model():
    cfg = NGPConfig.small()
    params = init_ngp(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.mark.parametrize("n", [1, 7, 256,
                               pytest.param(533, marks=pytest.mark.slow)])
def test_hash_encode_matches_reference(model, n):
    cfg, params = model
    pts = jax.random.uniform(jax.random.PRNGKey(n), (n, 3))
    got = ops.hash_encode(pts, params["grid"], cfg.grid)
    want = hashgrid.encode(pts, params["grid"], cfg.grid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-7)


def test_hash_encode_matches_reference_at_boundaries(model):
    """Clamp-at-boundary corners: points at/beyond the cube faces must hit
    the same clamped voxel rows in the kernel and the reference, on both
    dense and hashed levels."""
    cfg, params = model
    grid = cfg.grid
    dense_levels = [l for l in range(grid.n_levels) if grid.level_is_dense(l)]
    hashed = [l for l in range(grid.n_levels) if not grid.level_is_dense(l)]
    assert dense_levels and hashed, "config must exercise both level kinds"
    eps = np.float32(1e-6)
    corners = np.stack(np.meshgrid([0.0, 1.0], [0.0, 1.0], [0.0, 1.0],
                                   indexing="ij"), -1).reshape(-1, 3)
    pts = np.concatenate([
        corners,                                    # exact cube corners
        corners * (1 - eps) + eps / 2,              # just inside
        np.asarray([[1.0 - eps, 0.5, 0.5], [0.5, 1.0 - eps, 1.0 - eps],
                    [0.0, 0.0, 1.0], [1.0, 1.0, 1.0]], np.float32),
    ]).astype(np.float32)
    got = ops.hash_encode(jnp.asarray(pts), params["grid"], cfg.grid)
    want = hashgrid.encode(jnp.asarray(pts), params["grid"], cfg.grid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-7)
    # per-level-kind slices agree too (feature layout is [level, feat])
    F = grid.feature_dim
    for l in dense_levels[:1] + hashed[-1:]:
        np.testing.assert_allclose(
            np.asarray(got[:, l * F:(l + 1) * F]),
            np.asarray(want[:, l * F:(l + 1) * F]), rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("n", [3, 128, 300])
@pytest.mark.parametrize("paper_mlp", [False, True])
def test_fused_mlp_matches_reference(n, paper_mlp):
    cfg = NGPConfig.small(paper_mlp=paper_mlp)
    params = init_ngp(jax.random.PRNGKey(1), cfg)
    key = jax.random.PRNGKey(n)
    enc = jax.random.normal(key, (n, cfg.net.encoding_dim)) * 0.3
    dirs = jax.random.normal(key, (n, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    sig_k, rgb_k, geo_k = ops.fused_field(enc, dirs, params["mlps"], cfg.net)
    sig_r, geo_r = mlp_lib.density_apply(params["mlps"], enc)
    rgb_r = mlp_lib.color_apply(params["mlps"], geo_r, dirs, cfg.net.sh_degree)
    np.testing.assert_allclose(np.asarray(sig_k), np.asarray(sig_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(rgb_k), np.asarray(rgb_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(geo_k), np.asarray(geo_r),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("n", [5, 260])
def test_density_and_color_kernels_match(model, n):
    cfg, params = model
    key = jax.random.PRNGKey(n + 9)
    enc = jax.random.normal(key, (n, cfg.net.encoding_dim)) * 0.3
    dirs = jax.random.normal(key, (n, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    sig_k, geo_k = ops.density_mlp(enc, params["mlps"], cfg.net)
    sig_r, geo_r = mlp_lib.density_apply(params["mlps"], enc)
    np.testing.assert_allclose(np.asarray(sig_k), np.asarray(sig_r),
                               rtol=1e-4, atol=1e-6)
    col_k = ops.color_mlp(geo_r, dirs, params["mlps"], cfg.net)
    col_r = mlp_lib.color_apply(params["mlps"], geo_r, dirs,
                                cfg.net.sh_degree)
    np.testing.assert_allclose(np.asarray(col_k), np.asarray(col_r),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("R,S,group", [
    (4, 32, 2),
    pytest.param(37, 48, 4, marks=pytest.mark.slow),
    pytest.param(130, 192, 2, marks=pytest.mark.slow),
    (8, 64, 1)])
def test_volume_render_kernel_matches(R, S, group):
    key = jax.random.PRNGKey(R * S)
    A = -(-S // group)
    sig = jax.random.uniform(key, (R, S)) * 8
    anch = jax.random.uniform(jax.random.PRNGKey(1), (R, A, 3))
    dl = jnp.full((R, S), 0.02)
    rgb_k, acc_k = ops.volume_render(sig, anch, dl, group)
    rgb_r, acc_r = ref.ref_volume_render(sig, anch, dl, group)
    np.testing.assert_allclose(np.asarray(rgb_k), np.asarray(rgb_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r),
                               rtol=1e-4, atol=1e-5)


def test_volume_render_valid_mask():
    R, S, g = 6, 32, 2
    sig = jnp.ones((R, S)) * 5
    anch = jnp.ones((R, S // g, 3)) * 0.5
    dl = jnp.full((R, S), 0.05)
    valid = (jnp.arange(S) < 16)[None].repeat(R, 0)
    rgb_m, acc_m = ops.volume_render(sig, anch, dl, g, valid=valid)
    rgb_r, acc_r = ref.ref_volume_render(sig, anch, dl, g, valid=valid)
    np.testing.assert_allclose(np.asarray(rgb_m), np.asarray(rgb_r),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_kernel_field_fns_drive_full_pipeline(model):
    """The kernel-backed FieldFns must agree with the model-backed path."""
    cfg, params = model
    from repro.core import model as model_lib
    pts = jax.random.uniform(jax.random.PRNGKey(5), (97, 3)) * 1.2 - 0.1
    dirs = jax.random.normal(jax.random.PRNGKey(6), (97, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    kf = ops.field_fns(params, cfg)
    mf = model_lib.field_fns(params, cfg)
    sk, gk = kf.density(pts)
    sm, gm = mf.density(pts)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sm),
                               rtol=1e-4, atol=1e-6)
    ck = kf.color(gk, dirs)
    cm = mf.color(gm, dirs)
    np.testing.assert_allclose(np.asarray(ck), np.asarray(cm),
                               rtol=1e-4, atol=1e-6)


def test_march_hlo_carries_layer_scopes(model):
    """The pooled march's compiled HLO names its layers in op metadata —
    the hash encode, both MLPs and the compositing — for the profiler's
    per-op attribution."""
    from repro.core import pipeline
    from repro.serve import pool
    cfg, params = model
    acfg = pipeline.ASDRConfig(ns_full=16, candidates=(8, 16),
                               block_size=8, chunk=8)
    march = pool.batched_march(ops.field_fns(params, cfg), acfg)
    z = jnp.zeros((2, acfg.block_size, 3))
    budgets = jnp.full((2,), acfg.ns_full, jnp.int32)
    hlo = march.func.lower(*march.args, z, z, budgets).compile().as_text()
    for scope in ("hash_encode", "density_mlp", "color_mlp", "composite"):
        assert f"/{scope}/" in hlo, scope


def _pallas_interpret_flags(fn, *args):
    # fn must be a fresh function: a cached trace would keep the flag of
    # the platform it was first traced under
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [e.params["interpret"] for e in jaxpr.eqns
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("on_cpu", [True, False])
def test_interpret_mode_follows_platform(model, monkeypatch, on_cpu):
    """Every kernel interprets exactly on the CPU and lowers compiled on
    any other backend; the fused march refuses to run off the CPU."""
    from repro import kernels
    from repro.core import pipeline
    from repro.kernels import fused_mlp as FM, hash_encode as HE
    assert kernels.on_cpu() == (jax.default_backend() == "cpu")
    monkeypatch.setattr(kernels, "on_cpu", lambda: on_cpu)
    assert kernels.resolve_interpret() is on_cpu
    cfg, params = model
    wd, wc = ops.packed_weights(params["mlps"], cfg.net)
    x = jnp.zeros((FM.TILE, FM.P))
    pts = jnp.zeros((HE.TILE, HE.PPAD))
    for fn, args in [
            (lambda e, w: FM.density_call(e, w, cfg.net.geo_feature_dim),
             (x, wd)),
            (lambda c, w: FM.color_call(c, w), (x, wc)),
            (lambda p, t: HE.hash_encode_call(p, ops.grid_meta(cfg.grid), t),
             (pts, params["grid"]))]:
        flags = _pallas_interpret_flags(fn, *args)
        assert flags and all(bool(f) is on_cpu for f in flags), flags
    acfg = pipeline.ASDRConfig(ns_full=16, block_size=8, chunk=8,
                               candidates=(4, 8), march_backend="fused")
    rays = jnp.zeros((1, 8, 3))
    args = (ops.field_fns(params, cfg), acfg, rays, rays,
            jnp.ones((1,), jnp.int32))
    if on_cpu:
        pipeline.march_blocks(*args)
    else:
        with pytest.raises(ValueError, match="does not compile for TPU"):
            pipeline.march_blocks(*args)
