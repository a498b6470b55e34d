"""bench/trace_reduce.py on a hand-made event list and on a small trace
recorded on a v5e (``make_trace_fixture.py``)."""
from pathlib import Path

import pytest

from bench import trace_reduce

FIXTURE = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"
MS = 1_000_000


def synthetic():
    ops = [("%while.3 = (f32[4]{0}, s32[]) while((f32[4]{0}, s32[]) %t), body=%b", 0, 3 * MS),
           ("%fusion.1 = f32[8,2]{1,0:T(8,128)} fusion(f32[8]{0} %p), kind=kLoop", 0, 2 * MS),
           ("%gather.2 = f32[8]{0} gather(f32[8]{0} %a, s32[8]{0} %i)", 2 * MS, 3 * MS),
           ("%fusion.1 = f32[8,2]{1,0:T(8,128)} fusion(f32[8]{0} %p), kind=kLoop", 6 * MS, 8 * MS)]
    return {
        "devices": {"/device:TPU:0": {"ops": ops, "modules": [
            ("jit__march(7)", 0, 3 * MS), ("jit__probe_maps(9)", 6 * MS, 8 * MS)]}},
        "host": [("python", "bench.window", 0, 10 * MS),
                 ("python", "PjitFunction(_march)", 3 * MS, 5 * MS),
                 ("python", "host.sleep", 4500000, 6 * MS)],
        "planes": [],
    }


def test_synthetic_union_modules_and_gaps():
    r = trace_reduce.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.005)           # [0,3] and [6,8]
    assert r["modules"]["jit__march"] == {"seconds": pytest.approx(0.003), "launches": 1}
    ops = dict(r["breakdown"]["device_ops"])
    # own time: the while loop holds the fusion and the gather
    assert ops == {"fusion.1 fusion f32[8,2]": pytest.approx(0.004),
                   "gather.2 gather f32[8]": pytest.approx(0.001),
                   "while.3 while tuple": pytest.approx(0.0)}
    # gaps [3,6] (the dispatch covers 2 ms of it, the sleep 1.5) and [8,10]
    assert r["breakdown"]["idle_gaps"] == [["PjitFunction(_march)", pytest.approx(0.003)],
                                           ["host idle", pytest.approx(0.002)]]


def test_no_device_reads_nothing():
    ev = synthetic()
    ev["devices"] = {}
    assert trace_reduce.reduce(ev) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_recorded_v5e_trace():
    ev = trace_reduce.load(FIXTURE)
    assert list(ev["devices"]) == ["/device:TPU:0"]
    r = trace_reduce.reduce(ev)
    assert 0 < r["busy_s"] < r["window_s"]
    # the device clock runs ~1 ms ahead of the host's here, so the first
    # launch starts before the host opens the window
    assert r["modules"]["jit_tiny"]["launches"] >= 4
    assert r["breakdown"]["device_ops"][0][0].endswith("fusion f32[2048,2048]")
    # the 50 ms host sleep is the longest idle gap, and is named for it
    name, secs = r["breakdown"]["idle_gaps"][0]
    assert name == "host.sleep" and 0.045 < secs < 0.2
