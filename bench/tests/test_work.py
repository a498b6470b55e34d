"""bench/work.py against hand counts at both configurations."""
import json

from conftest import ROOT

from bench import peaks, work


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_paper_counts():
    w = work.per_sample(cfg("ingp-paper"))
    assert w["density_flops"] == 2 * (32 * 64 + 64 * 16) == 6144
    assert w["color_flops"] == 2 * (31 * 128 + 128 * 128 * 2 + 128 * 3) == 74240
    assert w["gather_bytes"] == 16 * 8 * 2 * 4 == 1024
    assert w["march_flops"] == 16 * 8 * 6 + 6144 + 74240 / 2 == 44032
    assert w["probe_flops"] == 16 * 8 * 6 + 6144 + 74240 == 81152


def test_base_counts():
    w = work.per_sample(cfg("ingp-base"))
    assert w["color_flops"] == 2 * (31 * 64 + 64 * 64 + 64 * 3) == 12544
    assert w["march_flops"] == 768 + 6144 + 6272 == 13184
    assert w["probe_flops"] == 768 + 6144 + 12544 == 19456


def test_march_is_bytes_bound_on_v5e():
    p = peaks.peaks("TPU v5 lite")
    t, bound = work.march_least_seconds(cfg("ingp-paper"), 131072, p)
    assert bound == "bytes"
    assert abs(t - 131072 * 1024 / 819e9) < 1e-12


def test_unknown_device_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9000")
