"""bench/work_nerfacto.py against hand counts at the nerfacto configuration."""
import json

import pytest

from conftest import ROOT

from bench import peaks, work_nerfacto


@pytest.fixture(scope="module")
def cfg():
    return json.loads((ROOT / "bench" / "configs" / "nerfacto.json").read_text())


def test_marched_sample(cfg):
    w = work_nerfacto.per_sample(cfg)
    assert w["march_bytes"] == 16 * 8 * 2 * 4 == 1024
    head = 2 * (63 * 64 + 64 * 64 + 64 * 3)
    assert w["march_flops"] == 16 * 8 * 6 + 2 * (32 * 64 + 64 * 16) + head == 23552


def test_proposal_sample(cfg):
    """Both proposal fields read 5 levels of 8 corners of 2 floats (320 B)
    and run 10-16-1; their mix over a ray's 256 + 96 samples is the same."""
    w = work_nerfacto.per_sample(cfg)
    assert w["proposal_bytes"] == 5 * 8 * 2 * 4 == 320
    assert w["proposal_flops"] == 5 * 8 * 6 + 2 * (10 * 16 + 16 * 1) == 592
    assert w["proposal_samples_per_ray"] == 352


def test_both_are_bytes_bound_on_v5e(cfg):
    p = peaks.peaks("TPU v5 lite")
    t, bound = work_nerfacto.march_least_seconds(cfg, 196608, p)
    assert bound == "bytes" and abs(t - 196608 * 1024 / 819e9) < 1e-12
    t, bound = work_nerfacto.proposal_least_seconds(cfg, 16384 * 352, p)
    assert bound == "bytes" and abs(t - 16384 * 352 * 320 / 819e9) < 1e-12
