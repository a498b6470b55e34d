"""Compile the serving path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not present: what it refuses here would be refused on the chip.
These are compiler verdicts, not chip runs — they say nothing of speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.  ``kernels.on_cpu`` is patched to False so the kernels
lower compiled, as they do on a TPU; the choice is made at trace time, so
traces cached under either choice are dropped around each test.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import nerfacto as nerfacto_config, tensorf_vm192
from repro.configs.ingp_asdr import CONFIG
from repro.core import hashgrid, mlp as mlp_lib, model as model_lib, nerfacto
from repro.core import pipeline, scene, tensorf
from repro.kernels import fused_mlp as FM
from repro.kernels import hash_encode as HE
from repro.kernels import ops

HBM_BYTES = 16 * 10 ** 9                  # one v5e chip
BYTES_LIMIT = 16909336064                 # its bytes_limit, as JAX reports it
ROWS = CONFIG.asdr.block_size * CONFIG.asdr.chunk   # samples per march step


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    pytest.importorskip("libtpu", reason="no TPU compiler installed")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture
def compiled(monkeypatch):
    jax.clear_caches()
    monkeypatch.setattr(kernels, "on_cpu", lambda: False)
    yield
    jax.clear_caches()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(sharding):
    shapes = jax.eval_shape(
        lambda: model_lib.init_ngp(jax.random.PRNGKey(0), CONFIG.model))
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), shapes)


def _serving_params(sharding):
    """The params the serving field binds on one v5e: the stacked tables,
    the MLPs, the ten corner tables (levels 0-9) and the six x-neighbour
    tables (levels 10-15) ``ops.field_fns`` derives from the tables for a
    chip of ``HBM_BYTES``."""
    params = _params(sharding)
    (corners,), (xrows,) = jax.eval_shape(lambda t: ops.corner_tables(
        (t,), (CONFIG.model.grid,), HBM_BYTES), params["grid"])
    assert (len(corners), len(xrows)) == (10, 6)
    return dict(params, **jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype),
                                       {"corners": corners, "xrows": xrows}))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel", ["density_call", "color_call"])
def test_fused_mlp_kernels_compile_at_paper_widths(chip, compiled, kernel):
    params = _params(chip)["mlps"]
    n = len(params["density"]) if kernel == "density_call" else len(
        params["color"])
    enc = _spec(chip, (ROWS, FM.P))
    w = _spec(chip, (n, FM.P, FM.P))
    geo = CONFIG.model.net.geo_feature_dim
    call = (partial(FM.density_call, geo_dim=geo) if kernel == "density_call"
            else FM.color_call)
    exe = _compile(call, enc, w)
    assert "tpu_custom_call" in exe.as_text()


def test_reference_march_block_compiles(chip, compiled):
    """One 4096-ray block of the engine's march: XLA-gather encode (corner
    rows at levels 0-9, x-neighbour rows at 10-15) feeding the compiled
    fused-MLP kernels."""
    cfg, acfg = CONFIG.model, CONFIG.asdr
    B = acfg.block_size

    def march(params, o, d, budget):
        return pipeline._march_block(ops._bound_field_fns(params, cfg), acfg,
                                     o, d, budget)

    exe = _compile(march, _serving_params(chip), _spec(chip, (B, 3)),
                   _spec(chip, (B, 3)), _spec(chip, (), jnp.int32))
    assert "tpu_custom_call" in exe.as_text()
    assert exe.memory_analysis().temp_size_in_bytes < HBM_BYTES // 2


def test_blocked_probe_fits_one_chip(chip, compiled):
    """The Phase-I probe of an 800x800 frame at 192 samples (4.9M samples)
    marched in blocks: its temporaries stay well under one chip's HBM."""
    cfg, acfg = CONFIG.model, CONFIG.asdr
    H, W = CONFIG.image_hw
    cam = scene.look_at_camera(H, W, 0.6, 0.5)
    fkey = (ops._bound_field_fns, cfg)   # fields.unbind's key for ops fields
    probe = partial(pipeline._probe_maps.__wrapped__, fkey, acfg,
                    (H, W, cam.focal))
    exe = _compile(lambda p, rot, org: probe(p, rot, org, None),
                   _serving_params(chip), _spec(chip, (3, 3)),
                   _spec(chip, (3,)))
    temps = exe.memory_analysis().temp_size_in_bytes
    assert temps < HBM_BYTES // 4, temps


def test_level9_corner_rows_build_in_slabs(chip, compiled):
    """Level 9's corner rows (294^3 voxels, 1.63 GB) build slab by slab:
    the build's temporaries stay under a quarter of the rows it writes."""
    g = CONFIG.model.grid
    res = g.level_resolution(9)
    build = partial(hashgrid.corner_table_slabs, res=res,
                    dense=g.level_is_dense(9))
    exe = _compile(build, _spec(chip, (g.table_size, g.feature_dim)))
    mem = exe.memory_analysis()
    assert mem.output_size_in_bytes >= res ** 3 * 8 * g.feature_dim * 4
    assert mem.temp_size_in_bytes < mem.output_size_in_bytes // 4, mem


def test_level15_xrow_rows_build_in_one_index_gathers(chip, compiled):
    """Level 15's x-neighbour rows (2^19 slots, 13 entries of 2 floats,
    54.5 MB) build from one one-index gather an entry: the compile takes
    seconds, and its temporaries stay under the rows it writes."""
    g = CONFIG.model.grid
    res = g.level_resolution(15)
    exe = _compile(partial(hashgrid.xrow_table, res=res),
                   _spec(chip, (g.table_size, g.feature_dim)))
    mem = exe.memory_analysis()
    assert mem.output_size_in_bytes >= hashgrid.xrow_bytes(g, 15) == 54_525_952
    assert mem.temp_size_in_bytes < mem.output_size_in_bytes, mem


def _tensorf_serving_params(sharding):
    """The params the serving field binds: the packed tables
    ``tensorf.field_fns`` derives, the basis and the MLP."""
    def serving():
        params = tensorf.init_params(jax.random.PRNGKey(0), tensorf_vm192.CONFIG.model)
        return dict(params, **tensorf.pack_tables(params))
    shapes = jax.eval_shape(serving)
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), shapes)


def test_tensorf_march_block_compiles(chip, compiled):
    """One 4096-ray block of TensoRF VM-192's march at its published widths
    (300^2 planes, 16/48 components, the 150-128-128-3 MLP, 1196 samples)
    over the packed tables, as the engine runs it: one one-index gather a
    packed plane or line row keeps the compile to seconds, and a chunk
    step's temporaries well under one chip's HBM."""
    cfg, acfg = tensorf_vm192.CONFIG.model, tensorf_vm192.CONFIG.asdr
    B = acfg.block_size

    def march(params, o, d, budget):
        return pipeline._march_block(tensorf._bound_field_fns(params, cfg), acfg,
                                     o, d, budget)

    exe = _compile(march, _tensorf_serving_params(chip), _spec(chip, (B, 3)),
                   _spec(chip, (B, 3)), _spec(chip, (), jnp.int32))
    assert exe.memory_analysis().temp_size_in_bytes < HBM_BYTES // 8


def _nerfacto_serving_params(sharding):
    """The params nerfacto's field binds on one v5e: the three grids'
    tables, their corner rows and x-neighbour rows under one budget of
    the chip's ``bytes_limit`` (main levels 0-9 and 10-13, both proposal
    grids whole), the folded head, the proposal MLPs."""
    m = nerfacto_config.CONFIG.model
    grids = (m.grid,) + m.proposal_grids

    def serving():
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        tables = [hashgrid.init_hashgrid(kk, g) for kk, g in zip(k, grids)]
        corners, xrows = ops.corner_tables(tuple(tables), grids, BYTES_LIMIT)
        mlps = mlp_lib.init_mlps(k[0], m.net)
        return {"grid": tables[0], "corners": corners[0], "xrows": xrows[0], "mlps": mlps,
                "proposals": [{"grid": t, "corners": c, "xrows": x,
                               "mlp": [jnp.zeros((10, 16)), jnp.zeros((16, 1))]}
                              for t, c, x in zip(tables[1:], corners[1:], xrows[1:])]}
    shapes = jax.eval_shape(serving)
    assert [(len(q["corners"]), len(q["xrows"])) for q in [shapes] + shapes["proposals"]
            ] == [(10, 4), (5, 0), (5, 0)]
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), shapes)


def test_nerfacto_proposal_sampler_compiles(chip, compiled):
    """Stage A of nerfacto for a 128x128 frame (256 + 96 proposal samples
    a ray over 5-level grids, both resamplings) as one executable, in
    blocks of 4,096 rays: its temporaries stay well under one chip."""
    m, acfg = nerfacto_config.CONFIG.model, nerfacto_config.CONFIG.asdr
    cam = scene.look_at_camera(128, 128, 0.6, 0.5)
    fkey = (nerfacto._bound_field_fns, m)
    exe = pipeline.proposal_edges.lower(
        fkey, acfg.block_size, (128, 128, cam.focal), _nerfacto_serving_params(chip),
        _spec(chip, (3, 3)), _spec(chip, (3,))).compile()
    assert exe.memory_analysis().temp_size_in_bytes < HBM_BYTES // 8


def test_nerfacto_interval_march_block_compiles(chip, compiled):
    """One 4,096-ray block of nerfacto's march at the proposal sampler's
    48 intervals, every sample's color on the fused-MLP kernels."""
    m, acfg = nerfacto_config.CONFIG.model, nerfacto_config.CONFIG.asdr
    B = acfg.block_size

    def march(params, o, d, budget, edges):
        return pipeline._march_block(nerfacto._bound_field_fns(params, m), acfg,
                                     o, d, budget, edges=edges)

    exe = _compile(march, _nerfacto_serving_params(chip), _spec(chip, (B, 3)),
                   _spec(chip, (B, 3)), _spec(chip, (), jnp.int32),
                   _spec(chip, (B, m.samples[-1] + 1)))
    assert "tpu_custom_call" in exe.as_text()
    assert exe.memory_analysis().temp_size_in_bytes < HBM_BYTES // 4


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="Mosaic refuses the VMEM vector gather table[idx] "
                          "in encode_level: 'Shape mismatch in input, "
                          "indices and output'")
def test_hash_encode_kernel_compiles(chip, compiled):
    g = CONFIG.model.grid
    _compile(HE.hash_encode_call,
             _spec(chip, (ROWS, HE.PPAD)),
             _spec(chip, (g.n_levels, 8), jnp.int32),
             _spec(chip, (g.n_levels, g.table_size, g.feature_dim)))
