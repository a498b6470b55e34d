"""The nerfacto cell's per-layer readers: the proposal executable's time
counts only where its launches in the trace equal the frames it
prepared, the march's where they equal the engine's batches, and each
roofline share is the least time over the device time."""
import json
import types

import pytest

from conftest import ROOT

from bench import harness, peaks, work_nerfacto
from bench.system.nerfacto import System


def _ctx(proposal_launches, frames, batches=4, march_launches=4):
    cfg = json.loads((ROOT / "bench" / "configs" / "nerfacto.json").read_text())
    modules = {System.MARCH_MODULE: {"seconds": 8.0, "launches": march_launches}}
    if proposal_launches:
        modules[System.PROPOSAL_MODULE] = {"seconds": 2.0, "launches": proposal_launches}
    served = [types.SimpleNamespace(stats={"probe_samples": 16384 * 352, "density_rays": 0,
                                           "samples_processed": 16384 * 48})
              for _ in range(frames)]
    return {"trace": {"modules": modules, "busy_s": 9.0, "window_s": 10.0},
            "engine_stats": {"batches": batches, "proposal_gather_rows_per_ray": 1760},
            "served": served, "system": System(cfg, None, None, None),
            "peak": peaks.peaks("TPU v5 lite"), "cfg": cfg}


@pytest.mark.parametrize("launches,frames,want", [
    (8, 8, 250.0),            # one launch a frame
    (9, 8, None),             # another executable shares the name
    (0, 8, None),             # the proposal executable renamed
    (0, 0, None),             # no frame prepared
])
def test_proposal_time_needs_a_launch_a_frame(launches, frames, want):
    assert harness.reader("proposal_ms.nerfacto")(_ctx(launches, frames)) == want


def test_rooflines_and_mfu():
    ctx = _ctx(8, 8)
    p = ctx["peak"]
    want = 100 * 8 * 16384 * 352 * 320 / p["hbm_bytes_per_s"] / 2.0
    assert harness.reader("proposal_roofline.nerfacto")(ctx) == pytest.approx(want)
    want = 100 * 8 * 16384 * 48 * 1024 / p["hbm_bytes_per_s"] / 8.0
    assert harness.reader("march_roofline.nerfacto")(ctx) == pytest.approx(want)
    assert harness.reader("march_batch_ms.nerfacto")(ctx) == 2000.0
    w = work_nerfacto.per_sample(ctx["cfg"])
    flops = 8 * 16384 * (48 * w["march_flops"] + 352 * w["proposal_flops"])
    assert harness.reader("mfu.nerfacto")(ctx) == pytest.approx(
        100 * flops / (10.0 * p["bf16_flops"]))
    assert harness.reader("march_roofline.nerfacto")(_ctx(8, 8, batches=5)) is None


def test_rows_and_idle():
    ctx = _ctx(8, 8)
    assert harness.reader("proposal_rows_per_ray.nerfacto")(ctx) == 1760.0
    assert harness.reader("device_idle_pct.nerfacto")(ctx) == pytest.approx(10.0)
    ctx["engine_stats"] = {"batches": 4}          # a program that does not count them
    assert harness.reader("proposal_rows_per_ray.nerfacto")(ctx) is None
