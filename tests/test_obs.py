"""Observability layer: tracing contract, metrics primitives, overhead.

Five property groups:

  * metrics — nearest-rank percentile (small-n off-by-one regression),
    bounded Series/Counter ledgers (>10k-round growth regression),
    registry exposition (Prometheus text + JSONL snapshots);
  * tracing — span nesting/lineage reconstruction, per-thread buffers
    draining without loss under concurrent writers, flight recorder
    firing exactly once per breach, Perfetto JSON schema round-trip
    (validated by tools/check_trace.py itself);
  * zero overhead when off — ``span()`` with no tracer installed is the
    shared NULL_SPAN singleton and adds no RETAINED allocations beyond
    a constant;
  * engine integration — frames + DETERMINISTIC_COUNTERS bit-identical
    with tracing on/off across executors {sync, threaded} x prefetch
    {0, 2} (the device executor case lives in tests/test_fleet.py,
    which owns the forced multi-device runtime), and an exported trace
    reconstructs a frame's full stage lineage with matching req/batch
    ids;
  * profiler bridge — under a JAX profiler session the spans land on
    the profiler's host plane with their ids, tracer or not, and frames
    + counters stay bit-identical.
"""
import json
import sys
import threading
import tracemalloc
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import fields, pipeline, scene
from repro.obs import (NULL_SPAN, Registry, TraceConfig, Tracer, export,
                       metrics as obs_metrics, percentile)
from repro.obs import trace as trace_lib
from repro.serve import stats as stats_lib
from repro.serve.render_engine import (RenderRequest, RenderServeConfig,
                                       RenderServingEngine)
from repro.serve.stats import DETERMINISTIC_COUNTERS

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import check_trace  # noqa: E402

ACFG = pipeline.ASDRConfig(ns_full=48, probe_stride=4, candidates=(8, 16, 32),
                           block_size=64, chunk=16, sort_by_opacity=False)


@pytest.fixture(scope="module")
def flds():
    return {"mic": fields.analytic_field_fns(scene.make_scene("mic"))}


def cam_at(theta):
    return scene.look_at_camera(16, 16, theta=theta, phi=0.5)


def traj(n=6):
    # poses repeat so laps 2+ exercise probe/radiance reuse under trace
    return [RenderRequest(rid=i, scene="mic", cam=cam_at(0.7 + 0.05 * (i % 3)))
            for i in range(n)]


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    assert trace_lib.active() is None
    yield
    assert trace_lib.active() is None, "a test leaked an installed tracer"


# ------------------------------------------------------------- percentile
def test_percentile_nearest_rank_small_n():
    """The PR-7 regression: int(n*q/100) made p50 of 2 samples the MAX.
    Nearest-rank is rank ceil(q/100 * n) clamped to [1, n]."""
    assert percentile([1.0, 2.0], 50.0) == 1.0
    assert percentile([2.0, 1.0], 50.0) == 1.0          # sorts internally
    assert percentile([1.0, 2.0], 99.0) == 2.0
    assert percentile([7.0], 50.0) == 7.0
    assert percentile([], 50.0) == 0.0
    assert percentile([1.0, 2.0, 3.0], 50.0) == 2.0
    assert percentile(range(1, 101), 99.0) == 99.0
    assert percentile(range(1, 101), 100.0) == 100.0
    assert percentile(range(1, 101), 0.0) == 1.0


def test_stats_percentile_is_the_shared_one():
    """serve.stats and benchmarks/common both re-export obs.metrics'."""
    assert stats_lib._percentile is percentile


# ------------------------------------------------- bounded engine ledgers
def test_counters_bounded_after_10k_rounds():
    """The unbounded march_ms/batches_per_round list leak, regressed:
    >10k simulated rounds must keep both ledgers at O(capacity) while
    march_rounds and the batches_per_round histogram stay exact."""
    c = stats_lib.EngineCounters()
    n = 12_000
    for i in range(n):
        c.note_round(0.001 * (1 + i % 7), 1 + i % 3)
        c.note_finalized({"rays_marched": 1, "rays_total": 2,
                          "samples_processed": 3, "samples_reused": 1,
                          "admit_stall_s": 0.001}, latency_s=0.01)
    assert len(c.march_ms.window()) == stats_lib.SERIES_CAPACITY
    assert len(c.latency_ms.window()) == stats_lib.SERIES_CAPACITY
    assert c.march_ms.count == n                 # all-time count survives
    assert len(c.batches_per_round) == 3         # keys = distinct counts
    st = stats_lib.engine_stats(c, {}, {}, None)
    assert st["march_rounds"] == n
    assert sum(st["batches_per_round"].values()) == n
    assert sum(k * v for k, v in st["batches_per_round"].items()) == \
        sum(1 + i % 3 for i in range(n))
    assert st["march_ms_p50"] > 0 and st["march_ms_p99"] >= st["march_ms_p50"]
    assert st["latency_ms_p50"] == pytest.approx(10.0)
    assert st["admit_stall_ms_p50"] == pytest.approx(1.0)


def test_histogram_merge_and_registry():
    h1 = obs_metrics.Histogram()
    h2 = obs_metrics.Histogram()
    for v in (0.5, 1.0, 2.0):
        h1.observe(v)
    for v in (4.0, 8.0):
        h2.observe(v)
    h1.merge(h2)
    assert h1.count == 5
    assert h1.percentile(99.0) >= 4.0

    reg = Registry()
    reg.counter("frames").inc(3)
    reg.gauge("fps").set(12.5)
    reg.histogram("span_ms_admission.wait").observe(2.0)
    text = reg.prometheus()
    assert "frames 3" in text
    assert "fps 12.5" in text
    assert "span_ms_admission_wait" in text      # prom-sanitized name
    snap = reg.snapshot()
    assert snap["frames"] == 3


def test_registry_jsonl_snapshot(tmp_path):
    reg = Registry()
    reg.counter("frames").inc(2)
    p = tmp_path / "metrics.jsonl"
    reg.jsonl_snapshot(p, extra={"round": 1})
    reg.counter("frames").inc(1)
    reg.jsonl_snapshot(p, extra={"round": 2})
    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert [ln["round"] for ln in lines] == [1, 2]
    assert [ln["metrics"]["frames"] for ln in lines] == [2, 3]
    assert all("ts" in ln for ln in lines)


# ----------------------------------------------------------- span tracing
def test_span_lineage_reconstruction():
    """Nested spans record parent = the innermost open span on their
    thread; a frame's stage chain reconstructs from parent edges."""
    tr = Tracer(TraceConfig())
    trace_lib.install(tr)
    try:
        with trace_lib.span("admission.wait", req=7):
            with trace_lib.span("stage_a.prepare", req=7):
                with trace_lib.span("probe.plan"):
                    pass
            with trace_lib.span("commit", req=7):
                pass
        tr.drain()
    finally:
        trace_lib.uninstall(tr)
    by_name = {s.name: s for s in tr.spans}
    assert len(tr.spans) == 4
    root = by_name["admission.wait"]
    assert root.parent == 0 and root.attrs["req"] == 7
    assert by_name["stage_a.prepare"].parent == root.sid
    assert by_name["probe.plan"].parent == by_name["stage_a.prepare"].sid
    assert by_name["commit"].parent == root.sid
    # sids are unique and t0 <= t1 everywhere
    assert len({s.sid for s in tr.spans}) == 4
    assert all(s.t0 <= s.t1 for s in tr.spans)


def test_threaded_buffers_drain_without_loss():
    """4 writer threads x 500 spans each, engine draining concurrently:
    every span arrives exactly once, none dropped."""
    tr = Tracer(TraceConfig())
    trace_lib.install(tr)
    stop = threading.Event()

    def writer(k):
        for i in range(500):
            with trace_lib.span("executor.run", worker=k, i=i):
                pass

    def drainer():
        while not stop.is_set():
            tr.drain()

    try:
        threads = [threading.Thread(target=writer, args=(k,),
                                    name=f"serve-stage-a_{k}")
                   for k in range(4)]
        d = threading.Thread(target=drainer, name="drain")
        d.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        d.join()
        tr.drain()
    finally:
        trace_lib.uninstall(tr)
    assert tr.dropped == 0
    assert len(tr.spans) == 2000
    seen = {(s.attrs["worker"], s.attrs["i"]) for s in tr.spans}
    assert len(seen) == 2000                     # exactly once each


def test_buffer_cap_drops_are_counted():
    tr = Tracer(TraceConfig(buffer_cap=10))
    trace_lib.install(tr)
    try:
        for i in range(25):
            with trace_lib.span("x", i=i):
                pass
        tr.drain()
    finally:
        trace_lib.uninstall(tr)
    assert len(tr.spans) == 10
    assert tr.dropped == 15


def test_flight_recorder_fires_exactly_once(tmp_path):
    """One dump per breach episode: the first breaching span writes the
    ring and disarms; later breaches are silent until rearm()."""
    rec = export.FlightRecorder(capacity=8)
    path = tmp_path / "flight.json"
    trig = rec.dump_on(export.stall_trigger(10.0), path)

    def span_ms(name, ms, sid):
        return trace_lib.Span(name, sid, 0, "engine", 0.0, ms * 1e-3, {})

    rec.record([span_ms("admission.wait", 1.0, 1)])
    assert trig.fired == 0 and not path.exists()
    fired = rec.record([span_ms("admission.wait", 50.0, 2),
                        span_ms("admission.wait", 99.0, 3)])
    assert fired == 1 and trig.fired == 1 and trig.fired_on == 2
    first = path.read_text()
    rec.record([span_ms("admission.wait", 75.0, 4)])
    assert trig.fired == 1                      # still disarmed
    assert path.read_text() == first
    rec.rearm()
    rec.record([span_ms("admission.wait", 80.0, 5)])
    assert trig.fired == 2 and trig.fired_on == 5
    # the dumped ring is itself a valid trace
    assert check_trace.check_file(path) == []


def test_replica_pid_export_and_fleet_merge(tmp_path):
    """ISSUE-9 satellite: TraceConfig.replica stamps every exported
    event's Chrome pid (one process group per replica) and a
    process_name metadata row; distinct-replica exports merge into one
    valid fleet timeline, duplicate pids are rejected."""
    paths = []
    for rep in (1, 2):
        tr = Tracer(TraceConfig())
        trace_lib.install(tr)
        try:
            with trace_lib.span("admission.wait", req=rep, scene="mic"):
                pass
            tr.drain()
        finally:
            trace_lib.uninstall(tr)
        path = tmp_path / f"trace_r{rep}.json"
        tr.cfg = TraceConfig(path=str(path), replica=rep)
        tr.finish()
        assert check_trace.check_file(path) == []
        data = json.loads(path.read_text())
        assert all(e["pid"] == rep for e in data["traceEvents"])
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   and e["args"]["name"] == f"replica-{rep}"
                   for e in data["traceEvents"])
        paths.append(path)
    merged = export.merge_chrome_traces(paths)
    assert merged["otherData"]["replicas"] == [1, 2]
    assert check_trace.validate(merged) == []
    out = tmp_path / "fleet.json"
    out.write_text(json.dumps(merged))
    assert check_trace.check_file(out) == []
    with pytest.raises(ValueError):
        export.merge_chrome_traces([paths[0], paths[0]])


def test_epoch_rebases_export_origin():
    """A shared epoch earlier than this tracer's wall start shifts its
    exported timestamps LATER by the same offset — per-replica exports
    land on one fleet clock."""
    tr = Tracer(TraceConfig())
    assert tr.export_origin() == tr.t_origin
    tr.cfg = TraceConfig(epoch=tr.wall_origin - 2.0)
    assert tr.export_origin() == pytest.approx(tr.t_origin - 2.0)


def test_chrome_trace_schema_roundtrip(tmp_path):
    """Exported Perfetto JSON round-trips through the schema validator
    (balanced spans, monotonic timestamps, known lanes)."""
    tr = Tracer(TraceConfig())
    trace_lib.install(tr)
    try:
        with trace_lib.span("admission.wait", req=0, scene="mic"):
            with trace_lib.span("stage_a.prepare", req=0):
                pass
        t = threading.Thread(
            target=lambda: trace_lib.span("executor.run",
                                          backend="threaded").__enter__()
            .__exit__(None, None, None),
            name="serve-stage-a_0")
        t.start()
        t.join()
        path = tmp_path / "trace.json"
        tr.cfg = TraceConfig(path=str(path))
        tr.finish()
    finally:
        trace_lib.uninstall(tr)
    assert check_trace.check_file(path) == []
    data = json.loads(path.read_text())
    evs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in evs} == {"admission.wait", "stage_a.prepare",
                                        "executor.run"}
    lanes = {e["args"]["name"] for e in data["traceEvents"]
             if e["ph"] == "M"}
    assert "serve-stage-a_0" in lanes
    # and the validator actually rejects a broken trace
    bad = dict(data)
    bad["traceEvents"] = data["traceEvents"] + [
        {"name": "orphan", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0,
         "dur": 1.0, "args": {"sid": 999, "parent": 555}}]
    assert check_trace.validate(bad)


# --------------------------------------------------- zero overhead when off
def test_disabled_mode_null_span_singleton():
    assert trace_lib.active() is None
    s1 = trace_lib.span("admission.wait", req=1, scene="mic")
    s2 = trace_lib.span("pool.dispatch", batch=2)
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with s1:
        pass                                    # enter/exit are no-ops
    trace_lib.instant("scenecache.hit")          # returns immediately


def test_disabled_mode_constant_retained_allocations():
    """No tracer installed: 10k instrumented call sites must retain no
    memory beyond a small constant (the kwargs dicts are transient)."""
    def admission_like(i):
        with trace_lib.span("admission.wait", req=i, scene="mic"):
            with trace_lib.span("stage_a.prepare", req=i):
                pass

    admission_like(0)                            # warm any lazy state
    tracemalloc.start()
    try:
        base, _ = tracemalloc.get_traced_memory()
        for i in range(10_000):
            admission_like(i)
        now, _ = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert now - base < 64 << 10, \
        f"disabled tracing retained {now - base} bytes over 10k admissions"


# ------------------------------------------------------ engine integration
def render_pair(flds, rcfg, n=6):
    eng = RenderServingEngine(flds, ACFG, rcfg)
    done = {r.rid: r for r in eng.render(traj(n))}
    st = eng.engine_stats()
    tr = eng.tracer
    eng.close()
    return done, st, tr


def test_trace_off_by_default(flds):
    assert RenderServeConfig().trace is None
    eng = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=2, blocks_per_batch=4))
    assert eng.tracer is None
    eng.close()


@pytest.mark.parametrize("workers,prefetch", [(0, 0), (0, 2), (2, 0), (2, 2)])
def test_bit_identity_tracing_on_off(flds, workers, prefetch, tmp_path):
    """Frames and every deterministic counter identical with tracing on
    vs off, for sync and threaded executors x prefetch {0, 2}.  (The
    device executor runs in tests/test_fleet.py's forced 4-device
    lane.)"""
    from repro.framecache import ProbeReuseConfig, RadianceReuseConfig
    base = RenderServeConfig(
        slots=2, blocks_per_batch=4,
        reuse=ProbeReuseConfig(refresh_every=0),
        radiance=RadianceReuseConfig(refresh_every=0),
        workers=workers, prefetch=prefetch)
    import dataclasses
    traced = dataclasses.replace(base, trace=TraceConfig(
        path=str(tmp_path / "t.json"), flight=True, stall_dump_ms=1e9))
    d_off, st_off, _ = render_pair(flds, base)
    d_on, st_on, tr = render_pair(flds, traced)
    assert d_off.keys() == d_on.keys()
    for rid in d_off:
        np.testing.assert_array_equal(d_off[rid].image, d_on[rid].image)
    for k in DETERMINISTIC_COUNTERS:
        assert st_off[k] == st_on[k], k
    assert tr is None or len(tr.spans) > 0
    assert check_trace.check_file(tmp_path / "t.json") == []


def test_engine_trace_reconstructs_lineage(flds, tmp_path):
    """A replayed frame's trace chains admission -> dispatch -> collect
    -> commit with matching req/batch ids (the acceptance lineage)."""
    from repro.framecache import ProbeReuseConfig
    from repro.scenecache import SceneCacheConfig
    path = tmp_path / "trace.json"
    rcfg = RenderServeConfig(
        slots=2, blocks_per_batch=4,
        reuse=ProbeReuseConfig(refresh_every=0),
        scenecache=SceneCacheConfig(byte_budget=4 << 20),
        prefetch=2, trace=TraceConfig(path=str(path)))
    eng = RenderServingEngine(flds, ACFG, rcfg)
    reqs = traj(6)
    done = eng.render(reqs)
    assert len(done) == len(reqs)
    spans = list(eng.tracer.spans)
    eng.close()

    names = {s.name for s in spans}
    for required in ("admission.wait", "stage_a.prepare", "stage_b.admit",
                     "commit", "pool.sweep", "pool.dispatch_round",
                     "pool.dispatch", "pool.collect", "probe.plan",
                     "probe.execute", "probe.commit"):
        assert required in names, f"missing span {required}"

    # every admitted request has an admission.wait span with its rid
    waits = [s for s in spans if s.name == "admission.wait"]
    assert {s.attrs["req"] for s in waits} == {r.rid for r in reqs}
    # stage_b.admit + commit nest under admission.wait with the same req
    by_sid = {s.sid: s for s in spans}
    for s in spans:
        if s.name == "stage_b.admit":
            parent = by_sid[s.parent]
            assert parent.name == "admission.wait"
            assert parent.attrs["req"] == s.attrs["req"]
    # batch ids pair dispatch with its collect, and reqs line up
    dispatches = {s.attrs["batch"]: s for s in spans
                  if s.name == "pool.dispatch"}
    collects = {s.attrs["batch"]: s for s in spans
                if s.name == "pool.collect"}
    assert dispatches and set(collects) == set(dispatches)
    for bid, d in dispatches.items():
        assert collects[bid].attrs["reqs"] == d.attrs["reqs"]
        assert d.attrs["scene"] == "mic"
        # the collect span is the host's wait for that batch's outputs:
        # it follows its dispatch on the same lane
        c = collects[bid]
        assert c.lane == d.lane and c.t0 >= d.t1 and c.dur_ms > 0.0
    # the exported file passes the validator too
    assert check_trace.check_file(path) == []


def test_engine_stats_is_registry_read(flds):
    """engine_stats() keys survive the registry round-trip exactly, and
    the same numbers appear in the Prometheus exposition."""
    eng = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=2, blocks_per_batch=4))
    eng.render(traj(4))
    st = eng.engine_stats()
    for k in ("frames", "latency_ms_p50", "latency_ms_p99",
              "admit_stall_ms_p50", "admit_stall_ms_p99",
              "march_ms_p50", "march_ms_p99", "march_rounds",
              "batches_per_round"):
        assert k in st, k
    assert st["frames"] == 4
    assert st["latency_ms_p99"] >= st["latency_ms_p50"] > 0
    text = eng.metrics.prometheus()
    assert f"frames {st['frames']}" in text
    assert max(st["batches_per_round"]) >= 1     # dict keyed by n_batches
    eng.close()


# ------------------------------------------------------ profiler bridge
def _host_events(logdir):
    """(name, stats) of every host-plane event in a profiler session's
    xplane file."""
    from jax.profiler import ProfileData
    path = sorted(Path(logdir).rglob("*.xplane.pb"))[-1]
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_profiler_session_records_engine_spans(flds, tmp_path):
    """With no tracer installed, an active profiler session turns the
    engine's spans into host-plane events carrying their req/batch ids,
    and the off path returns to NULL_SPAN after the session."""
    eng = RenderServingEngine(flds, ACFG, RenderServeConfig(
        slots=2, blocks_per_batch=4))
    eng.render(traj(2))                          # compile outside
    reqs = traj(4)
    with jax.profiler.trace(str(tmp_path)):
        assert trace_lib.span("pool.collect") is not NULL_SPAN
        eng.render(reqs)
    eng.close()
    assert trace_lib.span("pool.collect") is NULL_SPAN
    by_name = defaultdict(list)
    for name, stats in _host_events(tmp_path):
        by_name[name].append(stats)
    rids = {r.rid for r in reqs}
    for name in ("admission.wait", "engine.finalize"):
        assert {st["req"] for st in by_name[name]} == rids, name
    batches = {st["batch"] for st in by_name["pool.dispatch"]}
    assert batches and batches == {st["batch"]
                                   for st in by_name["pool.collect"]}
    assert all(isinstance(st["reqs"], str) for st in by_name["pool.collect"])


def test_profiled_span_also_records_into_tracer(tmp_path):
    """Profiler on and a tracer installed: one span lands in both, and
    attrs stamped after entry reach the tracer's copy."""
    tr = Tracer(TraceConfig())
    trace_lib.install(tr)
    try:
        with jax.profiler.trace(str(tmp_path)):
            with trace_lib.span("pool.collect", batch=3, reqs=[1, 2]) as sp:
                sp.attrs["kind"] = "late"
        tr.drain()
    finally:
        trace_lib.uninstall(tr)
    (s,) = tr.spans
    assert s.name == "pool.collect"
    assert s.attrs == {"batch": 3, "reqs": [1, 2], "kind": "late"}
    got = [st for name, st in _host_events(tmp_path) if name == "pool.collect"]
    assert got == [{"batch": 3, "reqs": "[1, 2]"}]


def test_bit_identity_profiler_on_off(flds, tmp_path):
    """Frames and every deterministic counter identical with a profiler
    session on vs off: the bridge only reads names and ids."""
    from repro.framecache import ProbeReuseConfig, RadianceReuseConfig
    rcfg = RenderServeConfig(
        slots=2, blocks_per_batch=4,
        reuse=ProbeReuseConfig(refresh_every=0),
        radiance=RadianceReuseConfig(refresh_every=0))
    d_off, st_off, _ = render_pair(flds, rcfg)
    with jax.profiler.trace(str(tmp_path)):
        d_on, st_on, _ = render_pair(flds, rcfg)
    assert d_off.keys() == d_on.keys()
    for rid in d_off:
        np.testing.assert_array_equal(d_off[rid].image, d_on[rid].image)
    for k in DETERMINISTIC_COUNTERS:
        assert st_off[k] == st_on[k], k
