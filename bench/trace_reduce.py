"""From a profiler trace (``.xplane.pb``) to device busy time, time per
XLA module and per operation, and the longest idle gaps with what the
host was doing in each.

A device plane is one named ``/device:<PLATFORM>:<n>``; its ``XLA Ops``
line holds the operations that ran and its ``XLA Modules`` line the
executables.  Host planes hold one line per thread, with the
``TraceAnnotation`` spans and JAX's dispatch events.  The window is the
host span named ``WINDOW`` when the trace has one, else the extent of
the device events.
"""
from __future__ import annotations

import re
from collections import defaultdict

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def events(profile) -> dict:
    """Plain lists from a ``jax.profiler.ProfileData``:
    ``{"devices": {plane: {"ops": [(name, t0, t1)], "modules": [...]}},
    "host": [(thread, name, t0, t1)]}``, times in ns."""
    out = {"devices": {}, "host": [], "planes": []}
    for plane in profile.planes:
        out["planes"].append([plane.name, [ln.name for ln in plane.lines][:12]])
        if _DEVICE.match(plane.name):
            d = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    d[key] += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
            out["devices"][plane.name] = d
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events if e.duration_ns > 0]
    return out


def load(path) -> dict:
    from jax.profiler import ProfileData
    return events(ProfileData.from_file(str(path)))


def _union(intervals, lo, hi):
    """Merged, clipped intervals."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def module_name(name: str) -> str:
    """``jit__march(123)`` -> ``jit__march``."""
    return name.split("(")[0].strip()


def op_label(text: str) -> str:
    """An ``XLA Ops`` event's HLO text -> ``<name> <opcode> <shape>``, e.g.
    ``fusion.196 fusion f32[1048576,2]``."""
    lhs, _, rhs = text.partition(" = ")
    if not rhs:
        return lhs.lstrip("%")
    if rhs.startswith("("):                 # a tuple: skip its balanced type
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        typ, rest = "tuple", rhs[i + 1:]
    else:
        typ, _, rest = rhs.partition(" ")
        typ = typ.split("{")[0]
    return f"{lhs.lstrip('%')} {rest.strip().split('(')[0]} {typ}"


def self_times(ops, lo, hi):
    """{label: seconds} of each op's own time inside [lo, hi]: its
    duration less that of the ops nested in it (a while loop holds its
    body's ops on the same line)."""
    out = defaultdict(float)
    stack = []                                # [label, end, child time]
    def close(item):
        out[item[0]] += item[3] - item[2]
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([op_label(n), e, 0, e - s])
    while stack:
        close(stack.pop())
    return {k: v * 1e-9 for k, v in out.items()}


def reduce(ev: dict, top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over the device planes),
    device seconds and launches per module, and the ``breakdown``: the
    ``top`` operations by their own device time and the ``top`` idle gaps,
    named by the host event that covers most of each gap."""
    devs = ev["devices"]
    if not devs or not any(d["ops"] or d["modules"] for d in devs.values()):
        return None
    spans = [(s, e) for _t, n, s, e in ev["host"] if n == WINDOW]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        lo = min(s for d in devs.values() for _n, s, _e in d["ops"] + d["modules"])
        hi = max(e for d in devs.values() for _n, _s, e in d["ops"] + d["modules"])
    busy, gaps = 0.0, []
    modules = defaultdict(lambda: [0.0, 0])
    ops = defaultdict(float)
    for d in devs.values():
        merged = _union([(s, e) for _n, s, e in (d["ops"] or d["modules"])], lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                 if edges[k + 1] > edges[k]]
        for n, s, e in d["modules"]:
            if s < hi and e > lo:
                m = modules[module_name(n)]
                m[0] += (min(e, hi) - max(s, lo)) * 1e-9
                m[1] += 1
        for k, v in self_times(d["ops"], lo, hi).items():
            ops[k] += v
    host = [(n, s, e) for _t, n, s, e in ev["host"] if n != WINDOW]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "host idle", 0
        for n, hs, he in host:
            c = min(he, e) - max(hs, s)
            if c > cover:
                best, cover = n, c
        named.append([best, (e - s) * 1e-9])
    return {
        "busy_s": busy * 1e-9 / len(devs),
        "window_s": (hi - lo) * 1e-9,
        "modules": {k: {"seconds": v[0], "launches": v[1]} for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(ops.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": named,
        },
    }
