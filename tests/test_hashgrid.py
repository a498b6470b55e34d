"""Hash-grid encoding: dense/hash split, interpolation, utilization."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hashgrid as hg


CFG = hg.HashGridConfig(n_levels=8, log2_table_size=14, max_resolution=256)


def test_level_split_matches_paper_rule():
    # dense iff (res+1)^3 fits the table — the paper's de-hash criterion
    for l in range(CFG.n_levels):
        res = CFG.level_resolution(l)
        assert CFG.level_is_dense(l) == ((res + 1) ** 3 <= CFG.table_size)
    # low levels dense, high levels hashed for this config
    assert CFG.level_is_dense(0)
    assert not CFG.level_is_dense(CFG.n_levels - 1)


def test_dense_indices_are_unique_and_in_range():
    res = CFG.level_resolution(0)
    coords = jnp.stack(jnp.meshgrid(
        *[jnp.arange(res + 1)] * 3, indexing="ij"), -1).reshape(-1, 3)
    idx = hg.level_indices(coords, res, True, CFG.table_size)
    assert int(idx.max()) < CFG.table_size
    assert len(np.unique(np.asarray(idx))) == (res + 1) ** 3


def test_hash_indices_in_range():
    res = CFG.level_resolution(CFG.n_levels - 1)
    key = jax.random.PRNGKey(0)
    coords = jax.random.randint(key, (500, 3), 0, res + 1)
    idx = hg.level_indices(coords, res, False, CFG.table_size)
    assert int(idx.min()) >= 0 and int(idx.max()) < CFG.table_size


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_trilinear_weights_sum_to_one(seed):
    """Property: encode of a constant table equals that constant
    (trilinear weights form a partition of unity)."""
    key = jax.random.PRNGKey(seed)
    pts = jax.random.uniform(key, (17, 3))
    table = jnp.full((CFG.table_size, 2), 3.25)
    for l in [0, CFG.n_levels - 1]:
        res = CFG.level_resolution(l)
        enc = hg.encode_level(pts, table, res, CFG.level_is_dense(l))
        np.testing.assert_allclose(np.asarray(enc), 3.25, rtol=1e-5)


def test_encode_at_vertex_returns_table_row():
    """At an exact grid vertex the encoding equals that vertex's entry."""
    l = 0
    res = CFG.level_resolution(l)
    key = jax.random.PRNGKey(1)
    table = jax.random.normal(key, (CFG.table_size, 2))
    v = jnp.asarray([[1, 2, 3]], jnp.float32)
    pts = v / res
    enc = hg.encode_level(pts, table, res, True)
    row = hg.level_indices(v.astype(jnp.int32), res, True, CFG.table_size)
    np.testing.assert_allclose(
        np.asarray(enc[0]), np.asarray(table[row[0]]), rtol=1e-4, atol=1e-6
    )


def test_full_encoding_shape_and_grad():
    # 4-level sub-config keeps the grad graph small (still dense + hashed)
    cfg = hg.HashGridConfig(n_levels=4, log2_table_size=12,
                            max_resolution=64)
    key = jax.random.PRNGKey(0)
    tables = hg.init_hashgrid(key, cfg)
    pts = jax.random.uniform(key, (33, 3))
    enc = hg.encode(pts, tables, cfg)
    assert enc.shape == (33, cfg.output_dim)
    g = jax.grad(lambda t: jnp.sum(hg.encode(pts, t, cfg) ** 2))(tables)
    assert not bool(jnp.any(jnp.isnan(g)))


def test_storage_utilization_improves_like_paper():
    """Paper Fig. 13: hybrid (de-hash + replicate) utilization >> naive."""
    cfg = hg.HashGridConfig()  # paper-scale 16 levels, 2^19
    u = hg.storage_utilization(cfg)
    assert u["hybrid_utilization"] > u["naive_utilization"]
    assert u["hybrid_utilization"] > 0.80  # paper reports 85.95%
    # copies only exist for dense (low-res) levels
    for l, c in u["copies_per_level"].items():
        if cfg.level_is_dense(l):
            assert c >= 1
        else:
            assert c == 1


def test_corner_levels_fit_a_quarter_of_the_device():
    """Under a 16 GiB chip the paper's grid serves levels 0-9 from corner
    rows (2.6 GB, within 4 GiB); level 10's 4.28 GB would not fit.
    Without a memory size the levels with res^3 <= T (0-5) do."""
    from repro.configs.ingp_asdr import CONFIG
    g = CONFIG.model.grid
    assert hg.corner_levels(g, 16 << 30) == tuple(range(10))
    assert sum(hg.corner_bytes(g, l) for l in range(10)) <= (16 << 30) // 4
    assert sum(hg.corner_bytes(g, l) for l in range(11)) > (16 << 30) // 4
    assert hg.corner_levels(g) == tuple(range(6))
    assert hg.corner_levels(g, 0) == ()


def _corner_case(case):
    """(grid, points, device memory) for the corner-row encode's cases."""
    from repro.configs.ingp_asdr import CONFIG, SMOKE
    pts = jax.random.uniform(jax.random.PRNGKey(3), (257, 3))
    if case == "smoke":
        return SMOKE.model.grid, pts, None
    if case == "config":
        return CONFIG.model.grid, pts, None
    if case == "hashed_collisions":
        # res 16 is hashed ((16+1)^3 > 2^12) yet 16^3 = 2^12 voxels fit
        return hg.HashGridConfig(n_levels=3, log2_table_size=12,
                                 max_resolution=64), pts, None
    # res 16, 20, 25, all hashed; 20^3 and 25^3 voxels exceed T = 2^12
    past = hg.HashGridConfig(n_levels=3, log2_table_size=12,
                             max_resolution=25)
    if case == "hashed_past_table":
        return past, pts, 1 << 30
    if case == "memory_cuts_levels":
        # a quarter of it holds levels 0-1's rows (774,144 B), not level 2's
        return past, pts, 4 * 800_000
    # every corner, edge midpoint and face centre of the unit cube
    g = jnp.asarray([0.0, 0.5, 1.0])
    cube = jnp.stack(jnp.meshgrid(g, g, g, indexing="ij"), -1).reshape(-1, 3)
    return SMOKE.model.grid, jnp.concatenate([cube, pts]), None


@pytest.mark.parametrize("case", ["smoke", "config", "hashed_collisions",
                                  "unit_cube_points", "hashed_past_table",
                                  "memory_cuts_levels"])
def test_corner_row_encode_is_bit_identical(case):
    """The levels ``corner_levels`` picks read one corner row a point;
    every other level gathers its 8 table rows.  The encoding is the
    same to the bit, at hashed levels past res^3 <= T too."""
    cfg, pts, memory = _corner_case(case)
    want = {"smoke": (0, 1), "config": (0, 1, 2, 3, 4, 5),
            "hashed_collisions": (0,), "unit_cube_points": (0, 1),
            "hashed_past_table": (0, 1, 2),
            "memory_cuts_levels": (0, 1)}[case]
    assert hg.corner_levels(cfg, memory) == want
    tables = hg.init_hashgrid(jax.random.PRNGKey(2), cfg)
    corners = hg.corner_tables(tables, cfg, memory)
    for l, c in zip(want, corners):
        res = cfg.level_resolution(l)
        assert c.shape == (res ** 3, 8 * cfg.feature_dim)
    if case in ("hashed_collisions", "hashed_past_table"):
        l = want[-1]
        res = cfg.level_resolution(l)
        assert not cfg.level_is_dense(l)
        assert (res ** 3 > cfg.table_size) == (case == "hashed_past_table")
        verts = jnp.stack(jnp.meshgrid(*[jnp.arange(res + 1)] * 3,
                                       indexing="ij"), -1).reshape(-1, 3)
        rows = hg.level_indices(verts, res, False, cfg.table_size)
        assert len(np.unique(np.asarray(rows))) < (res + 1) ** 3
    got = hg.encode(pts, tables, cfg, corners)
    ref = hg.encode(pts, tables, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("res,dense,planes", [
    (15, True, 4),      # dense; 4 does not divide 15: the last slab overlaps
    (20, False, 6),     # hashed past T = 2^12, 6 does not divide 20
    (20, False, 1),     # one plane a slab
    (20, False, None),  # the default: the whole level in one slab
    (25, False, 25),
])
def test_slab_built_corner_rows_equal_corner_table(res, dense, planes):
    """The slab build (vertex features gathered once, rows written in
    place slab by slab) gives ``corner_table``'s rows to the bit."""
    table = jax.random.normal(jax.random.PRNGKey(4), (1 << 12, 2))
    build = jax.jit(hg.corner_table_slabs, static_argnums=(1, 2, 3))
    np.testing.assert_array_equal(
        np.asarray(build(table, res, dense, planes)),
        np.asarray(hg.corner_table(table, res, dense)))
