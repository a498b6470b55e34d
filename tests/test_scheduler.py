"""Request-lifecycle scheduler invariants (serve/scheduler.py) — ISSUE-9.

FifoPolicy bit-identity vs the pre-scheduler engine (same frames, same
deterministic counters) across executors x prefetch; EDF ordering
determinism under deadline ties (unit + engine level); the shed-degrade
property that a request's budget never falls below its class's shed
floor plus the engine-level accounting invariant
``requests_shed + requests_full == frames``; open-loop arrival gating;
policy resolution; budget-scaled layouts; and the executor depth gauges
the scheduler publishes.
"""
import dataclasses
import math
import time

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import fields, pipeline, scene
from repro.framecache import probe as fc_probe
from repro.framecache import radiance as fc_radiance
from repro.serve import executor as executor_lib
from repro.serve import pool as pool_lib
from repro.serve.render_engine import (DeadlinePolicy, FifoPolicy,
                                       RenderRequest, RenderServeConfig,
                                       RenderServingEngine, RequestClass,
                                       ShedPolicy)
from repro.serve.scheduler import Scheduler, budget_scale_for, make_policy
from repro.serve.stats import DETERMINISTIC_COUNTERS, EngineCounters

ACFG = pipeline.ASDRConfig(ns_full=48, probe_stride=4, candidates=(8, 16, 32),
                           block_size=64, chunk=16, sort_by_opacity=False)
SIZE = 16


def cam_at(theta, phi=0.5):
    return scene.look_at_camera(SIZE, SIZE, theta=theta, phi=phi)


@pytest.fixture(scope="module")
def flds():
    return {"mic": fields.analytic_field_fns(scene.make_scene("mic"))}


def serve_cfg(workers=0, prefetch=2, slots=2, **kw):
    return RenderServeConfig(
        slots=slots, blocks_per_batch=4,
        reuse=fc_probe.ProbeReuseConfig(refresh_every=0),
        radiance=fc_radiance.RadianceReuseConfig(refresh_every=0),
        prefetch=prefetch, workers=workers, **kw)


def replay_traj(n=8):
    return [RenderRequest(rid=i, scene="mic", cam=cam_at(0.7 + 0.05 * (i % 3)))
            for i in range(n)]


def _req(rid, cls=None, arrival=0.0, theta=0.7):
    kw = {} if cls is None else {"cls": cls}
    return RenderRequest(rid=rid, scene="mic", cam=cam_at(theta),
                         arrival_s=arrival, **kw)


# ----------------------------------------------------------- bit-identity
def test_fifo_policy_bit_identity(flds):
    """The scheduler seam must be invisible at the default: policy=None,
    policy='fifo', and an explicit FifoPolicy() produce the same frame
    bytes and deterministic counters as each other at every executor
    (sync / threaded / device-config) x prefetch {0, 2} combination."""
    cases = [
        ("none-sync-p0", None, dict(workers=0, prefetch=0)),
        ("none-threaded-p2", None, dict(workers=2, prefetch=2)),
        ("name-sync-p2", "fifo", dict(workers=0, prefetch=2)),
        ("inst-sync-p0", FifoPolicy(), dict(workers=0, prefetch=0)),
        ("inst-sync-p2", FifoPolicy(), dict(workers=0, prefetch=2)),
        ("inst-threaded-p0", FifoPolicy(), dict(workers=2, prefetch=0)),
        ("inst-threaded-p2", FifoPolicy(), dict(workers=2, prefetch=2)),
        # devices>0 resolves per-host (DeviceExecutor, or SyncExecutor on
        # a single-device host) — either way the frames must match
        ("inst-device-p2", FifoPolicy(), dict(workers=0, prefetch=2,
                                              devices=2)),
    ]
    runs = {}
    for label, policy, kw in cases:
        eng = RenderServingEngine(flds, ACFG,
                                  serve_cfg(policy=policy, **kw))
        done = {r.rid: r for r in eng.render(replay_traj())}
        runs[label] = (done, eng.engine_stats())
        eng.close()
    ref_done, ref_st = runs["none-sync-p0"]
    for label, (done, st) in runs.items():
        for rid in ref_done:
            np.testing.assert_array_equal(
                ref_done[rid].image, done[rid].image,
                err_msg=f"frame {rid} differs at {label}")
        for c in DETERMINISTIC_COUNTERS:
            assert ref_st[c] == st[c], (label, c, ref_st[c], st[c])
    # the default class never sheds: all runs served full budget
    assert ref_st["requests_shed"] == 0
    assert ref_st["requests_full"] == ref_st["frames"]


# ------------------------------------------------------------ EDF ordering
def test_edf_select_deadline_order_and_ties():
    """Unit-level determinism: earliest absolute deadline wins; equal
    deadlines (including the no-deadline default class) resolve to the
    lowest queue position; un-arrived requests are invisible."""
    pol = DeadlinePolicy()
    rt50 = RequestClass("rt50", deadline_ms=50.0)
    rt10 = RequestClass("rt10", deadline_ms=10.0)
    q = [_req(0), _req(1, rt50), _req(2, rt10), _req(3, rt50)]
    assert pol.select(q, now_rel=0.0) == 2
    assert [r.rid for r in pol.prefetch_order(q, 0.0)] == [2, 1, 3, 0]
    # ties -> queue position, for any mix of equal keys
    q_tie = [_req(0, rt50), _req(1, rt50), _req(2, rt50)]
    assert pol.select(q_tie, 0.0) == 0
    assert [r.rid for r in pol.prefetch_order(q_tie, 0.0)] == [0, 1, 2]
    # a deadline that would win is invisible until it ARRIVES (absolute
    # deadline 0.02 + 10 ms = 0.03 beats rid 1's 0.05 — but only once
    # now_rel passes 0.02); a LATE arrival's absolute deadline can also
    # fall past an earlier peer's, so arriving never jumps the line
    q_fut = [_req(0, rt10, arrival=0.02), _req(1, rt50)]
    assert pol.select(q_fut, 0.0) == 1
    assert pol.select(q_fut, 0.025) == 0
    q_late = [_req(0, rt10, arrival=5.0), _req(1, rt50)]
    assert pol.select(q_late, 6.0) == 1
    assert pol.select([_req(0, arrival=1.0)], 0.0) is None


def test_edf_engine_admission_order(flds):
    """Engine-level EDF with slots=1 drains strictly by (deadline, queue
    position) — and reordering admissions never changes frame bytes
    (caches off: each request renders from its own pose alone)."""
    rt20 = RequestClass("rt20", deadline_ms=20.0)
    rt5 = RequestClass("rt5", deadline_ms=5.0)

    def traj():
        return [_req(0, theta=0.55), _req(1, rt20, theta=0.65),
                _req(2, rt20, theta=0.75), _req(3, rt5, theta=0.85)]

    cfg = RenderServeConfig(slots=1, blocks_per_batch=4, reuse=None,
                            radiance=None, prefetch=0)
    eng = RenderServingEngine(flds, ACFG,
                              dataclasses.replace(cfg, policy="edf"))
    done = eng.render(traj())
    eng.close()
    assert [r.rid for r in done] == [3, 1, 2, 0]

    eng_f = RenderServingEngine(flds, ACFG, cfg)
    ref = {r.rid: r for r in eng_f.render(traj())}
    eng_f.close()
    for r in done:
        np.testing.assert_array_equal(r.image, ref[r.rid].image)


# ------------------------------------------------------------ shed property
@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=3),
       st.floats(min_value=0.0, max_value=0.2),
       st.floats(min_value=1.0, max_value=400.0),
       st.floats(min_value=0.0, max_value=0.5))
def test_shed_never_degrades_past_floor(floor, ewma, deadline_ms, waited):
    """Property (ISSUE-9): whatever the projected service time, realized
    wait, and deadline, ``_maybe_shed`` never takes a request's tier
    past its class's shed floor — the degraded budget scale stays at or
    above the floor tier's scale — and every step is accounted."""
    cls = RequestClass("rt", deadline_ms=deadline_ms,
                       tiers=(1.0, 0.5, 0.25, 0.125), shed_floor=floor)
    counters = EngineCounters()
    sched = Scheduler("shed", counters)
    sched.ewma_service_s = ewma
    req = RenderRequest(rid=0, scene="mic", cam=None, cls=cls)
    assert req.tier == 0
    sched._maybe_shed(req, waited)
    assert 0 <= req.tier <= cls.shed_floor
    assert budget_scale_for(req) >= cls.tiers[cls.shed_floor]
    assert req.degrades == req.tier
    assert counters.shed_degrades == req.degrades
    # no projection basis, or no deadline -> never sheds
    for quiet_cls in (cls, dataclasses.replace(cls, deadline_ms=math.inf)):
        quiet = RenderRequest(rid=1, scene="mic", cam=None, cls=quiet_cls)
        cold = Scheduler("shed", EngineCounters())
        cold.ewma_service_s = 0.0 if quiet_cls is cls else ewma
        cold._maybe_shed(quiet, waited)
        assert quiet.tier == 0 and quiet.degrades == 0


def test_shed_accounting_and_class_stats(flds):
    """Engine-level accounting: a warmed EWMA plus an impossible 5 ms
    deadline sheds every rt request exactly to the floor tier, and
    ``requests_shed + requests_full == frames`` with per-class ledgers
    splitting the traffic (floored requests may still miss — they are
    counted, never dropped)."""
    warm = RequestClass("warm", deadline_ms=1.0)   # earliest deadline:
    rt = RequestClass("rt", deadline_ms=5.0,       # admitted first, sheds
                      tiers=(1.0, 0.5, 0.25), shed_floor=2)   # nothing

    def traj():
        return [_req(0, warm, theta=0.55)] + [
            _req(i, rt, theta=0.55 + 0.1 * i) for i in range(1, 6)]

    cfg = RenderServeConfig(slots=1, blocks_per_batch=4, reuse=None,
                            radiance=None, prefetch=0, policy="shed")
    eng = RenderServingEngine(flds, ACFG, cfg)
    done = eng.render(traj())
    st_out = eng.engine_stats()
    eng.close()
    assert st_out["frames"] == 6
    assert st_out["requests_shed"] + st_out["requests_full"] \
        == st_out["frames"]
    # rid 0 admits on a cold EWMA (never sheds) and warms it; every rt
    # request then projects >> 5 ms slack and degrades to the floor
    assert st_out["requests_shed"] == 5
    assert st_out["shed_degrades"] == 10
    for r in done:
        if r.cls.name == "rt":
            assert r.tier == r.cls.shed_floor
            assert budget_scale_for(r) == r.cls.tiers[r.cls.shed_floor]
        else:
            assert r.tier == 0 and r.degrades == 0
    assert set(st_out["class_stats"]) == {"rt", "warm"}
    led = st_out["class_stats"]["rt"]
    assert led["frames"] == 5 and led["shed"] == 5
    assert st_out["deadline_misses"] >= led["deadline_misses"]


# -------------------------------------------------------- open-loop traffic
def test_open_loop_arrival_gating(flds):
    """A queued request is invisible until its ``arrival_s`` passes: the
    engine idles through the gap, and the latency clock starts at the
    ARRIVAL, not at enqueue."""
    cfg = RenderServeConfig(slots=2, blocks_per_batch=4, reuse=None,
                            radiance=None, prefetch=0)
    eng = RenderServingEngine(flds, ACFG, cfg)
    eng.render([_req(0)])               # absorb compile time
    t0 = time.time()
    done = eng.render([_req(1), _req(2, arrival=0.4, theta=0.75)])
    wall = time.time() - t0
    eng.close()
    assert [r.rid for r in done] == [1, 2]
    assert wall >= 0.4                  # rid 2 never admitted early
    # rid 2's latency excludes the 0.4 s it had not yet arrived
    assert done[1].latency_s <= wall - 0.35


# ----------------------------------------------- where the latency goes
PHASES = ("queue_s", "admit_stall_s", "march_s", "finalize_s")


@pytest.mark.parametrize("open_loop", [False, True])
def test_frame_phases_sum_to_latency(flds, open_loop):
    """Per frame, waiting for admission + the blocking admission +
    marching + finalizing is the whole latency (one clock)."""
    eng = RenderServingEngine(flds, ACFG, serve_cfg(slots=2))
    eng.render([_req(0)])               # absorb compile time
    reqs = [_req(i, arrival=0.05 * i if open_loop else 0.0,
                 theta=0.7 + 0.05 * i) for i in range(1, 6)]
    done = eng.render(reqs)
    eng.close()
    assert len(done) == len(reqs)
    for r in done:
        parts = [r.stats[k] for k in PHASES]
        assert min(parts) >= 0.0, dict(zip(PHASES, parts))
        assert sum(parts) == pytest.approx(r.latency_s, abs=1e-3)
    # a closed batch of 5 frames in 2 slots: later frames queue
    assert open_loop or max(r.stats["queue_s"] for r in done) > 0.0


@pytest.mark.parametrize("open_loop", [False, True])
def test_march_gap_and_arrival_wait(flds, open_loop):
    """The engine's march gap lies within the call's wall time; arrival
    sleeps count only in an open loop with a gap before the first
    arrival."""
    eng = RenderServingEngine(flds, ACFG, serve_cfg(slots=2))
    eng.render([_req(0)])               # absorb compile time
    st0 = eng.engine_stats()
    reqs = [_req(1, arrival=0.2 if open_loop else 0.0),
            _req(2, arrival=0.2 if open_loop else 0.0, theta=0.75),
            _req(3, theta=0.8)]
    t0 = time.perf_counter()
    eng.render(reqs)
    wall = time.perf_counter() - t0
    st1 = eng.engine_stats()
    eng.close()
    gap = st1["march_gap_s"] - st0["march_gap_s"]
    waited = st1["arrival_wait_s"] - st0["arrival_wait_s"]
    assert 0.0 < gap <= wall
    assert gap + waited <= wall
    if open_loop:
        assert waited > 0.0
    else:
        assert waited == 0.0


# ----------------------------------------------------------------- plumbing
def test_make_policy_resolution():
    assert type(make_policy(None)) is FifoPolicy
    assert not make_policy(None).shed
    assert type(make_policy("fifo")) is FifoPolicy
    assert type(make_policy("edf")) is DeadlinePolicy
    pol = make_policy("shed")
    assert type(pol) is ShedPolicy and pol.shed and pol.headroom == 1.0
    mine = ShedPolicy(headroom=2.0)
    assert make_policy(mine) is mine
    with pytest.raises(ValueError):
        make_policy("lifo")


def test_budget_scaled_counts_floor_and_identity():
    """Layout degrade point: scaled counts round UP, never below one
    sample per ray; scale 1.0 is the identity (the bit-identity path
    skips the scaling ops entirely)."""
    counts = jnp.array([0, 1, 2, 7, 48], jnp.int32)
    out = np.asarray(pool_lib._scale_counts(counts, 0.25))
    np.testing.assert_array_equal(out, [1, 1, 1, 2, 12])
    # scale 1.0 is identity on real (positive) counts; build_layout
    # additionally skips the call entirely at 1.0 (the bit-identity path)
    np.testing.assert_array_equal(
        np.asarray(pool_lib._scale_counts(counts[1:], 1.0)),
        np.asarray(counts[1:]))


def test_executor_depth_gauges(flds):
    """Satellite: every executor reports queue depth, and the scheduler
    publishes it through the metrics registry during admission."""
    ex = executor_lib.SyncExecutor()
    assert ex.depth() == {"pending": 0, "inflight": 0}
    ex.submit("k", lambda: 1)
    assert ex.depth()["pending"] == 1
    ex.take("k")
    assert ex.depth()["pending"] == 0
    ex.close()

    ex = executor_lib.ThreadedExecutor(2)
    ex.submit("a", lambda: time.sleep(0.02) or 1)
    d = ex.depth()
    assert d["pending"] >= 1 and d["inflight"] >= 0
    assert ex.take("a") == 1
    assert ex.depth()["pending"] == 0
    ex.close()

    eng = RenderServingEngine(flds, ACFG, serve_cfg(0, 2))
    eng.render(replay_traj(3))
    snap = eng.metrics.snapshot()
    eng.close()
    assert "executor_pending_depth" in snap
    assert "executor_inflight_depth" in snap
