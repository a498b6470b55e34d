"""Per-layer readers: the march's device time counts only where its
launches in the trace equal the engine's batches, and a metric split by
cells (``<stem>.<cells>``) with no reader of its own reads its stem's."""
import types

import pytest

from bench import harness
from bench.system.ingp import System


def _ctx(launches, batches, density_rays=0):
    modules = {System.MARCH_MODULE: {"seconds": 2.0, "launches": launches}} if launches else {}
    frame = types.SimpleNamespace(stats={"density_rays": density_rays})
    return {"trace": {"modules": modules, "busy_s": 1.0, "window_s": 4.0},
            "engine_stats": {"batches": batches}, "served": [frame],
            "system": System(None, None, None, None)}


@pytest.mark.parametrize("launches,batches,density_rays,want", [
    (4, 4, 0, 500.0),          # one executable, every launch a batch
    (5, 4, 0, None),           # another jitted partial shares the name
    (4, 4, 4096, None),        # density-only marches share it too
    (0, 3, 0, None),           # the march renamed: nothing by that name
])
def test_march_time_needs_matching_launches(launches, batches, density_rays, want):
    got = harness.reader("march_batch_ms.fresh")(_ctx(launches, batches, density_rays))
    assert got == want


@pytest.mark.parametrize("name", ["device_idle_pct.fresh", "device_idle_pct.session"])
def test_split_metric_reads_its_stem(name):
    assert harness.reader(name)(_ctx(4, 4)) == pytest.approx(75.0)


def test_metric_without_reader_fails():
    with pytest.raises(FileNotFoundError):
        harness.reader("no_such_metric.fresh")
