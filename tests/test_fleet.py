"""Multi-device fleet lane (run via ``make test-fleet``).

These tests exercise the DeviceExecutor and the sharded scene cache on a
REAL multi-device jax runtime, made cheap on CPU-only CI by
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the HomebrewNLP
trick from SNIPPETS.md).  They carry their own pytest marker (``fleet``)
and a dedicated Makefile / CI invocation, because the device count is
locked at jax init — the default fast tier must keep seeing one device.

Covered here (ISSUE-6):
  * DeviceExecutor-vs-SyncExecutor bit-identity (frames + deterministic
    counters) for devices {1, 2, 4} x prefetch {0, 2};
  * commit ordering under an adversarial slow-probe DEVICE (the
    earliest-submitted speculation finishes last);
  * graceful fallback to SyncExecutor when only one device exists;
  * Stage-A placement actually lands on secondary devices, round-robin,
    while the march owns device 0;
  * a two-replica fleet over one ShardedSceneCache matches the plain
    single sync engine bit-exactly while sharing blocks cross-replica.
"""
import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import fields, pipeline, scene
from repro.framecache import probe as fc_probe
from repro.framecache import radiance as fc_radiance
from repro.scenecache import SceneCacheConfig, ShardedSceneCache
from repro.serve import executor as executor_lib
from repro.serve.render_engine import (RenderRequest, RenderServeConfig,
                                       RenderServingEngine)
from repro.serve.stats import DETERMINISTIC_COUNTERS

pytestmark = [
    pytest.mark.fleet,
    pytest.mark.skipif(
        jax.device_count() < 4,
        reason="fleet lane needs 4 host devices — run via make test-fleet "
               "(XLA_FLAGS=--xla_force_host_platform_device_count=4)"),
]

ACFG = pipeline.ASDRConfig(ns_full=48, probe_stride=4, candidates=(8, 16, 32),
                           block_size=64, chunk=16, sort_by_opacity=False)
SIZE = 16


def cam_at(theta, phi=0.5):
    return scene.look_at_camera(SIZE, SIZE, theta=theta, phi=phi)


@pytest.fixture(scope="module")
def flds():
    return {"mic": fields.analytic_field_fns(scene.make_scene("mic"))}


def serve_cfg(devices=0, prefetch=2, slots=2):
    return RenderServeConfig(
        slots=slots, blocks_per_batch=4,
        reuse=fc_probe.ProbeReuseConfig(refresh_every=0),
        radiance=fc_radiance.RadianceReuseConfig(refresh_every=0),
        prefetch=prefetch, devices=devices)


def replay_traj(n=8, offset=0):
    # poses repeat every 3 requests: laps 2+ exercise warp reuse, full
    # radiance hits, AND speculation racing the in-flight sources
    return [RenderRequest(rid=offset + i, scene="mic",
                          cam=cam_at(0.7 + 0.05 * (i % 3)))
            for i in range(n)]


# ----------------------------------------------------------- determinism
def test_device_executor_bit_identity(flds):
    """Placement moves WHERE Stage A runs, never WHAT commits: frames
    and all commit-determined counters must be bit-identical to the
    synchronous single-device run for devices {1, 2, 4} x prefetch
    {0, 2} — devices=4 clamps to the 3 available secondaries."""
    eng0 = RenderServingEngine(flds, ACFG, serve_cfg(0, 0))
    ref = {r.rid: r for r in eng0.render(replay_traj())}
    st0 = eng0.engine_stats()
    eng0.close()
    for devices in (1, 2, 4):
        for prefetch in (0, 2):
            eng = RenderServingEngine(flds, ACFG,
                                      serve_cfg(devices, prefetch))
            assert isinstance(eng.executor, executor_lib.DeviceExecutor)
            assert len(eng.executor.devices) == min(devices, 3)
            done = {r.rid: r for r in eng.render(replay_traj())}
            st = eng.engine_stats()
            eng.close()
            for rid in ref:
                np.testing.assert_array_equal(
                    ref[rid].image, done[rid].image,
                    err_msg=f"frame {rid} differs at devices={devices}, "
                            f"prefetch={prefetch}")
            for c in DETERMINISTIC_COUNTERS:
                assert st0[c] == st[c], (devices, prefetch, c, st0[c], st[c])


def test_commit_ordering_under_adversarial_slow_device(flds, monkeypatch):
    """Commits happen on the engine thread in ADMISSION order even when
    per-device completion order is inverted: the earliest-submitted
    probes are stubbed slowest (a stalled device), so later speculations
    on other devices finish first — finish order, frames, and counters
    must still match the synchronous run."""
    real_execute = fc_probe.execute_probe_plan
    lock = threading.Lock()
    seen = {"n": 0}

    def slow_execute(fns, acfg, cam, plan, probe_key=None, rcfg=None):
        with lock:
            i = seen["n"]
            seen["n"] += 1
        if plan.kind in ("fresh", "refresh"):
            time.sleep(0.12 if i < 2 else 0.0)   # earliest probes slowest
        return real_execute(fns, acfg, cam, plan, probe_key=probe_key,
                            rcfg=rcfg)

    # distinct fresh poses: every admission pays a probe, all speculated
    def traj():
        return [RenderRequest(rid=i, scene="mic", cam=cam_at(0.55 + 0.1 * i))
                for i in range(6)]

    cfg = RenderServeConfig(
        slots=1, blocks_per_batch=4,
        reuse=fc_probe.ProbeReuseConfig(max_angle_deg=0.01,
                                        max_translation=1e-4),
        radiance=None, prefetch=4, devices=0)
    eng_s = RenderServingEngine(flds, ACFG, cfg)
    done_s = eng_s.render(traj())

    monkeypatch.setattr(fc_probe, "execute_probe_plan", slow_execute)
    eng_d = RenderServingEngine(flds, ACFG,
                                dataclasses.replace(cfg, devices=4))
    assert isinstance(eng_d.executor, executor_lib.DeviceExecutor)
    done_d = eng_d.render(traj())
    eng_d.close()

    assert [r.rid for r in done_d] == [r.rid for r in done_s]
    by_rid = {r.rid: r for r in done_s}
    for r in done_d:
        np.testing.assert_array_equal(r.image, by_rid[r.rid].image)
    st_s, st_d = eng_s.engine_stats(), eng_d.engine_stats()
    for c in DETERMINISTIC_COUNTERS:
        assert st_s[c] == st_d[c], (c, st_s[c], st_d[c])


# -------------------------------------------------------------- placement
def test_stage_a_lands_on_secondary_devices():
    """The placement rule itself: submissions round-robin over the
    secondary devices; device 0 (the march's device) never executes
    speculation; results are consumable on device 0."""
    import jax.numpy as jnp
    ex = executor_lib.DeviceExecutor()
    assert [d.id for d in ex.devices] == [d.id for d in jax.devices()[1:]]
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    n = 2 * len(ex.devices)
    for i in range(n):
        ex.submit(i, lambda: f(jnp.full((4,), 3.0)))
    placed = []
    for i in range(n):
        out = ex.take(i)
        (dev,) = out.devices()
        placed.append(dev.id)
        np.testing.assert_array_equal(np.asarray(out), np.full((4,), 7.0))
    ex.close()
    assert 0 not in placed
    expected = [d.id for d in jax.devices()[1:]]
    assert placed == expected * 2, f"round-robin broken: {placed}"
    assert ex.runs == [2] * len(ex.devices), ex.runs


def test_param_field_probe_runs_on_secondary_device():
    """A probe of a param-backed field (params passed to the jitted probe
    as arguments) placed by the DeviceExecutor computes on the secondary
    device, its params copied there, and equals the probe on device 0."""
    from repro.configs.ingp_asdr import SMOKE
    from repro.core import model as model_lib
    params = model_lib.init_ngp(jax.random.PRNGKey(0), SMOKE.model)
    fns = model_lib.field_fns(params, SMOKE.model)
    cam = cam_at(0.6)
    want = np.asarray(pipeline.probe_phase(fns, ACFG, cam)[0])
    ex = executor_lib.DeviceExecutor()
    ex.submit(0, lambda: pipeline.probe_phase(fns, ACFG, cam)[0])
    got = ex.take(0)
    ex.close()
    assert ex.runs[0] == 1
    assert got.devices() == {ex.devices[0]}
    np.testing.assert_array_equal(np.asarray(got), want)


def test_single_device_fallback(flds, monkeypatch):
    """A devices>0 config on a single-device host degrades to the
    bit-identical SyncExecutor instead of failing (the same engine
    binary serves a laptop and a fleet host)."""
    monkeypatch.setattr(executor_lib, "_available_devices",
                        lambda: [jax.devices()[0]])
    ex = executor_lib.make_executor(0, devices=2)
    assert isinstance(ex, executor_lib.SyncExecutor)
    eng = RenderServingEngine(flds, ACFG, serve_cfg(devices=2))
    assert isinstance(eng.executor, executor_lib.SyncExecutor)
    done = eng.render(replay_traj(4))
    assert len(done) == 4 and all(r.image is not None for r in done)
    eng.close()


# -------------------------------------------------------------------- obs
def test_device_executor_tracing_bit_identity(flds, tmp_path):
    """The tracing on/off bit-identity gate's DEVICE-executor leg (the
    sync/threaded legs live in tests/test_obs.py, which only sees one
    device): frames + deterministic counters identical with the tracer
    on, Stage-A placement spans land on the serve-dev* lanes with their
    device attr, and the exported trace passes the format validator."""
    import sys
    from pathlib import Path as _P

    from repro.obs import TraceConfig

    sys.path.insert(0, str(_P(__file__).resolve().parent.parent / "tools"))
    import check_trace

    for prefetch in (0, 2):
        ref_eng = RenderServingEngine(flds, ACFG, serve_cfg(2, prefetch))
        ref = {r.rid: r for r in ref_eng.render(replay_traj())}
        st_ref = ref_eng.engine_stats()
        ref_eng.close()

        path = tmp_path / f"fleet_trace_{prefetch}.json"
        cfg = dataclasses.replace(
            serve_cfg(2, prefetch), trace=TraceConfig(path=str(path)))
        eng = RenderServingEngine(flds, ACFG, cfg)
        assert isinstance(eng.executor, executor_lib.DeviceExecutor)
        done = {r.rid: r for r in eng.render(replay_traj())}
        st = eng.engine_stats()
        spans = list(eng.tracer.spans)
        eng.close()

        for rid in ref:
            np.testing.assert_array_equal(ref[rid].image, done[rid].image)
        for c in DETERMINISTIC_COUNTERS:
            assert st_ref[c] == st[c], (prefetch, c)
        if prefetch > 0:
            runs = [s for s in spans if s.name == "executor.run"]
            assert runs, "no placement spans with prefetch on"
            assert all(s.lane.startswith("serve-dev") for s in runs)
            assert all(s.attrs["backend"] == "device" and "device" in s.attrs
                       for s in runs)
        assert check_trace.check_file(path) == []


# ------------------------------------------------------------------ fleet
def test_two_replica_fleet_sharded_cache_identity(flds):
    """Two engine replicas (device executors) over one ShardedSceneCache
    replay the same pose set: every frame bit-identical to a plain
    single sync engine, cross-replica block hits > 0, and every shard
    within its byte budget."""
    plain = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=2, blocks_per_batch=4, reuse=None, radiance=None))
    ref = {r.rid: r for r in plain.render(replay_traj(6))}

    shared = ShardedSceneCache(SceneCacheConfig(byte_budget=8 << 20),
                               shards=4)
    cfg = RenderServeConfig(slots=2, blocks_per_batch=4, reuse=None,
                            radiance=None, devices=2)
    engines = [RenderServingEngine(flds, ACFG, cfg, scenecache=shared)
               for _ in range(2)]
    done = [engines[0].render(replay_traj(6)),
            engines[1].render(replay_traj(6, offset=100))]
    for frames in done:
        for r in frames:
            np.testing.assert_array_equal(r.image, ref[r.rid % 100].image)
    # replica 1 replayed replica 0's poses: its blocks came from the store
    assert engines[1].engine_stats()["scene_block_hits"] > 0
    st = shared.stats()
    assert all(b <= st["per_shard_budget"]
               for b in st["per_shard_resident_bytes"])
    for eng in engines:
        eng.close()
    shared.close()
