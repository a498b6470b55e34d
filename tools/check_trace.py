#!/usr/bin/env python
"""Validate exported Chrome/Perfetto trace files (the obs contract).

  python tools/check_trace.py out/trace.json [...]   validate files
  python tools/check_trace.py                        self-test (make lint)

Checks, per file (see src/repro/obs/README.md for the format contract):

  * structure — ``traceEvents`` list present; every non-metadata event
    is a complete span (``ph: "X"``) with name / pid / tid / ts / dur
    and an ``args`` dict carrying its ``sid`` and ``parent``;
  * balanced spans — sids unique; every nonzero parent refers to a span
    in the file whose [ts, ts+dur] interval CONTAINS the child's (same
    lane — parents are the innermost open span on the recording
    thread), up to a float-rounding epsilon;
  * monotonic timestamps — ts >= 0 and dur >= 0 everywhere;
  * known lanes — every tid is declared by a ``thread_name`` metadata
    event, and every lane name matches the taxonomy (engine main
    thread, serve-stage-a workers, serve-dev device queues,
    scenecache-fetch pool, or a pytest/driver thread);
  * replica namespaces — spans, lanes, and parent links are validated
    PER ``pid``: a merged fleet timeline (export.merge_chrome_traces)
    carries one process group per replica, and sids are only unique
    within their replica's tracer.

With no arguments the script self-tests: it records a tiny two-thread
span tree through ``repro.obs`` itself, exports it, and validates the
result — so ``make lint`` exercises the exporter + this checker without
needing a rendered trace on disk.  Exit code 1 on any finding.
"""
from __future__ import annotations

import json
import re
import sys
import tempfile
from pathlib import Path

# lane taxonomy: the thread names the serving stack records under
# (obs/trace.py lane = thread name) plus generic driver threads
LANE_PATTERNS = (
    r"MainThread",
    r"engine.*",
    r"serve-stage-a.*",          # ThreadedExecutor workers
    r"serve-dev\d+.*",           # DeviceExecutor per-device queues
    r"scenecache-fetch.*",       # ShardedSceneCache fetch pool
    r"Thread-\d+.*",             # bare threading.Thread (tests/drivers)
    r"Dummy-\d+.*",
    r"(pytest|asyncio).*",
)
_LANE_RE = re.compile("^(%s)$" % "|".join(LANE_PATTERNS))
_EPS_US = 50.0      # parent/child containment slack (clock rounding)


def validate(data: dict) -> list:
    """All contract violations in one parsed trace dict (empty = ok)."""
    errs = []
    events = data.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    lanes = {}              # (pid, tid) -> lane name
    spans = {}              # (pid, sid) -> event: sids are per-replica
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph == "M":
            if ev.get("name") == "thread_name":
                lanes[(ev.get("pid"), ev.get("tid"))] = \
                    ev.get("args", {}).get("name", "")
            continue
        if ph != "X":
            errs.append(f"event {i}: unexpected phase {ph!r}")
            continue
        for field in ("name", "pid", "tid", "ts", "dur"):
            if field not in ev:
                errs.append(f"event {i}: missing {field!r}")
        ts, dur = ev.get("ts", 0), ev.get("dur", 0)
        if ts < 0:
            errs.append(f"event {i} ({ev.get('name')}): negative ts {ts}")
        if dur < 0:
            errs.append(f"event {i} ({ev.get('name')}): negative dur {dur}")
        args = ev.get("args")
        if not isinstance(args, dict) or "sid" not in args \
                or "parent" not in args:
            errs.append(f"event {i} ({ev.get('name')}): args must carry "
                        f"sid + parent")
            continue
        key = (ev.get("pid"), args["sid"])
        if key in spans:
            errs.append(f"event {i}: duplicate sid {key[1]} in "
                        f"pid {key[0]}")
        spans[key] = ev
    # balanced spans: parent exists (same replica) and contains the
    # child (same lane)
    for (pid, sid), ev in spans.items():
        parent = ev["args"]["parent"]
        if parent == 0:
            continue
        pev = spans.get((pid, parent))
        if pev is None:
            errs.append(f"span {sid} ({ev['name']}): parent {parent} "
                        f"not in trace")
            continue
        if pev["tid"] != ev["tid"]:
            errs.append(f"span {sid} ({ev['name']}): parent on a "
                        f"different lane")
        if ev["ts"] < pev["ts"] - _EPS_US or \
                ev["ts"] + ev["dur"] > pev["ts"] + pev["dur"] + _EPS_US:
            errs.append(f"span {sid} ({ev['name']}): not contained in "
                        f"parent {parent} ({pev['name']})")
    # known lanes: every span's tid declared, every lane name known
    for (pid, sid), ev in spans.items():
        if (pid, ev["tid"]) not in lanes:
            errs.append(f"span {sid} ({ev['name']}): tid {ev['tid']} has "
                        f"no thread_name metadata")
    for (pid, tid), name in lanes.items():
        if not _LANE_RE.match(name):
            errs.append(f"lane tid={tid}: unknown lane name {name!r}")
    return errs


def check_file(path) -> list:
    try:
        data = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable trace: {e}"]
    return validate(data)


def self_test() -> list:
    """Record a tiny two-thread span tree and validate its export."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    import threading

    from repro.obs import TraceConfig, Tracer, install, uninstall

    tracer = Tracer(TraceConfig())
    install(tracer)
    try:
        with tracer.span("admission.wait", req=0, scene="mic"):
            with tracer.span("stage_a.prepare", req=0):
                pass
        t = threading.Thread(
            target=lambda: tracer.span("executor.run",
                                       backend="threaded").__enter__()
            .__exit__(None, None, None),
            name="serve-stage-a_0")
        t.start()
        t.join()
        tracer.drain()
        with tempfile.TemporaryDirectory() as d:
            path = Path(d) / "trace.json"
            tracer.cfg = TraceConfig(path=str(path))
            tracer.finish()
            errs = check_file(path)
        n = len(tracer.spans)
        if n != 3:
            errs.append(f"self-test recorded {n} spans, expected 3")
        return errs
    finally:
        uninstall(tracer)


def main(argv) -> int:
    if argv:
        bad = 0
        for path in argv:
            errs = check_file(path)
            for e in errs:
                print(f"{path}: {e}")
            bad += bool(errs)
            if not errs:
                print(f"[check_trace] {path}: ok")
        return 1 if bad else 0
    errs = self_test()
    for e in errs:
        print(f"self-test: {e}")
    print(f"[check_trace] self-test: {'FINDINGS' if errs else 'ok'}")
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
