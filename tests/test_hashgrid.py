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


def _xrow_case(case):
    """(grid, points) for the x-neighbour encode's cases; every level of
    each grid is hashed."""
    pts = jax.random.uniform(jax.random.PRNGKey(5), (513, 3))
    if case == "hashed_collisions":
        # res 16, 25, 40, 63 at T = 2^12: (res+1)^3 > T, so slots collide
        return hg.HashGridConfig(n_levels=4, log2_table_size=12,
                                 max_resolution=63), pts
    if case == "every_trailing_ones_count":
        # res 32 and 64 = 2^k: x = res - 1 has k trailing ones, and every
        # x in [0, res - 1] is a point's voxel
        xs = (jnp.arange(64) + 0.5) / 64
        grid = jnp.stack([xs, jnp.roll(xs, 7), jnp.roll(xs, 29)], -1)
        return hg.HashGridConfig(n_levels=2, log2_table_size=12,
                                 base_resolution=32, max_resolution=64), \
            jnp.concatenate([grid, pts])
    if case == "masks_past_the_table":
        # T = 2^6 below 2^(k+1) = 2^7 at res 64: the neighbour's XOR mask
        # wraps to T - 1
        return hg.HashGridConfig(n_levels=1, log2_table_size=6,
                                 base_resolution=64, max_resolution=64), pts
    # every corner, edge midpoint and face centre of the unit cube
    g = jnp.asarray([0.0, 0.5, 1.0])
    cube = jnp.stack(jnp.meshgrid(g, g, g, indexing="ij"), -1).reshape(-1, 3)
    return hg.HashGridConfig(n_levels=4, log2_table_size=12,
                             max_resolution=63), jnp.concatenate([cube, pts])


@pytest.mark.parametrize("case", ["hashed_collisions", "every_trailing_ones_count",
                                  "masks_past_the_table", "unit_cube_points"])
def test_xrow_encode_is_bit_identical(case):
    """A hashed level read from x-neighbour rows (4 gathered rows a point,
    the x-upper corner picked by the trailing ones of x) encodes to the
    bit what its 8 table rows do, collisions included; so does ``encode``
    with every level from them."""
    cfg, pts = _xrow_case(case)
    tables = hg.init_hashgrid(jax.random.PRNGKey(6), cfg)
    xrows = []
    for l in range(cfg.n_levels):
        res = cfg.level_resolution(l)
        assert not cfg.level_is_dense(l)
        xrows.append(hg.xrow_table(tables[l], res))
        assert xrows[-1].shape == (cfg.table_size, (res.bit_length() + 1) * cfg.feature_dim)
        np.testing.assert_array_equal(
            np.asarray(hg.encode_xrow_level(pts, xrows[-1], res)),
            np.asarray(hg.encode_level(pts, tables[l], res, False)))
    if case == "hashed_collisions":
        res = cfg.level_resolution(0)
        verts = jnp.stack(jnp.meshgrid(*[jnp.arange(res + 1)] * 3,
                                       indexing="ij"), -1).reshape(-1, 3)
        rows = hg.level_indices(verts, res, False, cfg.table_size)
        assert len(np.unique(np.asarray(rows))) < (res + 1) ** 3
    if case == "every_trailing_ones_count":
        for res in cfg.level_resolutions():
            x = np.asarray(hg._voxel(pts, res)[0][:, 0])
            ones = {bin(v ^ (v + 1)).count("1") - 1 for v in x.tolist()}
            assert ones == set(range(int(np.log2(res)) + 1))
    np.testing.assert_array_equal(
        np.asarray(hg.encode(pts, tables, cfg, xrows=tuple(xrows))),
        np.asarray(hg.encode(pts, tables, cfg)))


@pytest.mark.parametrize("res,log2_t", [(16, 12), (63, 12), (64, 6), (2048, 19)])
def test_xrow_table_equals_its_definition(res, log2_t):
    """Row h of the x-neighbour rows holds T[h], then T[h ^ m_t] for
    t = 0 .. floor(log2(res)), m_t = (2^(t+1) - 1) & (T - 1): to the bit."""
    T = 1 << log2_t
    table = jax.random.normal(jax.random.PRNGKey(7), (T, 2))
    got = np.asarray(jax.jit(hg.xrow_table, static_argnums=1)(table, res))
    k = int(np.floor(np.log2(res)))
    assert got.shape == (T, 2 * (k + 2))
    h = np.arange(T)
    want = [np.asarray(table)] + [np.asarray(table)[h ^ (((2 << t) - 1) & (T - 1))]
                                  for t in range(k + 1)]
    np.testing.assert_array_equal(got, np.concatenate(want, axis=1))


@pytest.mark.parametrize("case", ["ingp_16gib", "nerfacto_v5e", "no_memory", "zero_memory"])
def test_xrow_levels_fill_the_quarter_past_the_corner_rows(case):
    """After the corner levels, a field's next hashed levels take
    x-neighbour rows while both kinds fit a quarter of the device: on a
    16 GiB chip the paper's grid serves 0-9 from corner rows and 10-15
    from x-neighbour rows (289 MB), 34 indices a point; nerfacto's three
    grids on a v5e's bytes_limit leave at least main levels 10-13; with
    no memory size, or none, no level takes them."""
    from repro.configs.ingp_asdr import CONFIG
    from repro.configs import nerfacto as nerfacto_config
    g = CONFIG.model.grid
    if case == "nerfacto_v5e":
        m = nerfacto_config.CONFIG.model
        grids, memory = (m.grid,) + m.proposal_grids, 16909336064
    else:
        grids = (g,)
        memory = {"ingp_16gib": 16 << 30, "no_memory": None, "zero_memory": 0}[case]
    corners = hg.field_corner_levels(grids, memory)
    xrows = hg.field_xrow_levels(grids, memory)
    used = sum(hg.corner_bytes(gg, l) for gg, c in zip(grids, corners) for l in c) + sum(
        hg.xrow_bytes(gg, l) for gg, x in zip(grids, xrows) for l in x)
    if case == "ingp_16gib":
        assert corners == (tuple(range(10)),) and xrows == (tuple(range(10, 16)),)
        assert sum(hg.xrow_bytes(g, l) for l in xrows[0]) == 289_406_976
        assert used <= memory // 4
        assert hg.rows_per_point(g, corners[0], xrows[0]) == 10 + 6 * 4 == 34
    elif case == "nerfacto_v5e":
        assert corners == (tuple(range(10)), tuple(range(5)), tuple(range(5)))
        assert xrows[0][:4] == (10, 11, 12, 13) and xrows[1:] == ((), ())
        assert used <= memory // 4
        nxt = len(corners[0]) + len(xrows[0])
        if nxt < g.n_levels:
            assert used + hg.xrow_bytes(g, nxt) > memory // 4
    else:
        assert xrows == ((),)
        assert hg.rows_per_point(g, corners[0], xrows[0]) == len(corners[0]) + 8 * (
            16 - len(corners[0]))
