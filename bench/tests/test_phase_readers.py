"""Readers of where a frame's latency and the march's idle time go: they
read the program's counters, and return None where the program does not
report them (an older program), rather than fail."""
import types

import pytest

from bench import harness


def _ctx(stats, frames):
    return {"engine_stats": stats, "window_host_s": 50.0,
            "served": [types.SimpleNamespace(stats=f) for f in frames]}


@pytest.mark.parametrize("name", ["march_gap_pct.fresh", "march_gap_pct.session"])
def test_march_gap_share_of_window(name):
    read = harness.reader(name)
    assert read(_ctx({"march_gap_s": 2.0}, [])) == pytest.approx(4.0)
    assert read(_ctx({"batches": 3}, [])) is None


@pytest.mark.parametrize("name,key", [("frame_queue_p90_ms.session", "queue_s"),
                                      ("frame_march_p90_ms.session", "march_s")])
def test_frame_phase_p90(name, key):
    read = harness.reader(name)
    frames = [{key: s / 10.0} for s in range(1, 11)]     # 0.1 .. 1.0 s
    assert read(_ctx({}, frames)) == pytest.approx(900.0)
    assert read(_ctx({}, [{"admit_stall_s": 0.1}])) is None
    assert read(_ctx({}, [])) is None
