"""The traffic generator: deterministic by seed, views spaced past probe
reuse, orbits apart, the same arrivals for every seed."""
import math

import numpy as np

from bench import traffic


def same(a, b):
    return (len(a) == len(b) and all(
        ra == rb and da == db and all(np.array_equal(np.asarray(x), np.asarray(y))
                                      for x, y in zip(ca, cb))
        for (ra, ca, da), (rb, cb, db) in zip(a, b)))


def test_fresh_is_deterministic_by_seed():
    spec = traffic.load("fresh-128")
    big = 2 ** 31 + 12345
    assert same(traffic.requests(spec, big, 50, 20), traffic.requests(spec, big, 50, 20))
    assert not same(traffic.requests(spec, big, 50, 20), traffic.requests(spec, big + 1, 50, 20))


def test_fibonacci_views_beyond_probe_reuse():
    spec = traffic.load("fresh-128")
    theta, phi = traffic.fibonacci(spec["views"]["points"])
    d = np.stack([np.cos(phi) * np.cos(theta), np.cos(phi) * np.sin(theta), np.sin(phi)], -1)
    cos = np.clip(d @ d.T, -1, 1)
    np.fill_diagonal(cos, -1)
    nearest = np.degrees(np.arccos(cos.max(axis=1)))
    assert nearest.min() > 4.0          # ProbeReuseConfig.max_angle_deg
    chord = 2 * spec["views"]["radius"] * np.sin(np.radians(nearest.min()) / 2)
    assert chord > 0.08                 # ProbeReuseConfig.max_translation
    rids = [r for r, _, _ in traffic.requests(spec, 3, 50, 40)]
    assert rids == list(range(40))


def test_sessions_deterministic_and_apart():
    spec = traffic.load("session-64")
    a = traffic.requests(spec, 99, 50)
    assert same(a, traffic.requests(spec, 99, 50))
    b = traffic.requests(spec, 100, 50)
    assert [d for _, _, d in a] == [d for _, _, d in b]     # same arrivals
    assert not same(a, b)                                   # other poses
    rate = spec["loop"]["rate_per_s"]
    assert abs(len(a) - rate * 50) <= spec["views"]["users"]
    rng = np.random.default_rng([99, 1])
    starts = traffic._start_poses(spec["views"], rng, spec["views"]["users"])
    for i, p in enumerate(starts):
        for q in starts[:i]:
            assert math.degrees(traffic._angle(p, q)) >= spec["views"]["min_start_sep_deg"]


def test_warm_up_takes_the_mix_paths():
    fresh = traffic.warm_up(traffic.load("fresh-128"))
    sess = traffic.warm_up(traffic.load("session-64"))
    assert len(fresh) == len(sess) == 2
    step = np.linalg.norm(sess[0][4] - sess[1][4])
    assert 0 < step < 0.08
    assert np.linalg.norm(fresh[0][4] - fresh[1][4]) > 0.08
