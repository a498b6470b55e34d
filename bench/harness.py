"""One run of one cell: set-up, the measured window, the metrics and the
check.  Everything cell-specific is found by name: the configuration
file named in ``BENCHMARK.json``, its architecture's system adapter
(``bench/system/<arch>.py``) and reference (``bench/reference/<arch>.py``),
the traffic mix (``bench/traffic/<mix>.json``), one reader per metric
(``bench/metrics/<metric>.py``) and the cell's limits
(``bench/limits/<workload>.json``)."""
from __future__ import annotations

import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import check, peaks, trace_reduce, traffic as traffic_lib
from bench.stats import CompileClock

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def cell_spec(workload: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    cell = dict(cells[workload])
    cell["config_spec"] = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return cell


def reader(name: str, bdir: Path = HERE):
    """``metrics/<name>.py``; for a quantity split by the cells it reports
    in (``<stem>.<cells>``) with no reader of its own, ``metrics/<stem>.py``."""
    path = bdir / "metrics" / f"{name}.py"
    if not path.exists():
        path = bdir / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(jax, n: int) -> dict:
    devs = jax.devices()[:n]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": n,
            "memory_peak_bytes": int(peak)}


def closed_frames(system, params, cam, spec: dict, seconds: float) -> int:
    """Frames for a closed batch that keeps the engine busy for about
    ``seconds``: every block marched at the full budget, one probe each,
    whole rounds of ``slots`` frames."""
    a, rc = system.acfg, system.rcfg
    step = system.time_chunk_step(params)
    probe = system.time_probe(params, cam)
    H, W = spec["frame"]
    blocks = -(-H * W // a.block_size)
    frame_s = probe + blocks * (-(-a.ns_full // a.chunk)) * step
    rounds = max(1, round(seconds / (frame_s * rc.slots)))
    log(f"sizing: chunk_step_s={step} probe_s={probe} frame_estimate_s={frame_s} "
        f"frames={rounds * rc.slots}")
    return rounds * rc.slots


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True, root: Path = ROOT, observe: dict | None = None) -> dict | None:
    """Run the cell once; returns the result object (None when the
    machine lacks the chips the cell asks for).  ``observe``, if given,
    receives the run's context (requests, latencies, stats)."""
    cell = cell_spec(workload, root)
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        log(f"needs {cell['chips']} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
        return None
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()

    cfg = json.loads((root / cell["config_spec"]["file"]).read_text())
    bdir = root / "bench"
    spec = traffic_lib.load(cell["traffic"], bdir)
    system = importlib.import_module(f"bench.system.{cfg['architecture']}").build(cfg)
    reference = importlib.import_module(f"bench.reference.{cfg['architecture']}")
    params = reference.make_params(cfg, seed)
    warm = traffic_lib.warm_up(spec)
    system.warm_up(params, warm)
    frames = None
    if spec["loop"]["kind"] == "closed":
        frames = closed_frames(system, params, warm[0], spec, seconds)
    offered = traffic_lib.requests(spec, seed, seconds, frames)
    cams = {rid: cam for rid, cam, _ in offered}
    reqs = [system.request(rid, cam, due) for rid, cam, due in offered]
    engine = system.engine(params)
    jax.block_until_ready(params)
    compiles0, compile_s0 = clock.compiles, clock.total()

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = system.serve(engine, reqs)
        t_close = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    compiles = clock.compiles - compiles0
    log(f"window: frames={len(reqs)} host_s={t_close - t0} compiles_in_window={compiles} "
        f"compile_s_in_window={clock.total() - compile_s0} setup_s={setup_s} "
        f"cache_hits={clock.cache_hits}")
    device = device_info(jax, cell["chips"])
    served = [r for r in reqs if r.done_at is not None and r.image is not None]
    failed = len(reqs) - sum(
        1 for r in served
        if r.image.shape == (r.cam.height, r.cam.width, 3) and np.isfinite(r.image).all())
    stats = engine.engine_stats()
    engine.close()
    latency = [r.done_at - (t0 + r.arrival_s) for r in served]
    if spec["loop"]["kind"] == "open":
        log(f"open loop: frames_due={len(reqs)} served={len(served)} "
            f"generator_lateness_ms=0 (arrivals are gated by the engine itself; "
            f"latency counts from each frame's due time)")
    ctx = {
        "cfg": cfg, "spec": spec, "system": system, "setup_s": setup_s,
        "t0": t0, "requests": reqs, "served": served, "latency_s": latency,
        "engine_stats": stats, "device": device, "trace": None,
        "window_host_s": max([r.done_at for r in served], default=t_close) - t0,
    }
    if observe is not None:
        observe.update(ctx)
    if trace:
        files = sorted(Path(tdir).rglob("*.xplane.pb"))
        ev = trace_reduce.load(files[-1]) if files else None
        ctx["trace"] = trace_reduce.reduce(ev) if ev else None
        log(f"trace planes: {json.dumps(ev and ev['planes'])}")
        shutil.rmtree(tdir, ignore_errors=True)
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            log(f"trace modules: {json.dumps(ctx['trace']['modules'])}")
    ctx["peak"] = peaks.peaks(device["kind"]) if require_chip else None

    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        v = reader(m["name"], bdir)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the check, once the window has closed and its memory is read
    del engine
    k = spec["check_pixels_per_frame"]
    o, d, got = check.rays(reference, [(r.rid, cams[r.rid], r.image) for r in served], seed, k)
    t_ref = time.perf_counter()
    ref = reference.render(cfg, params, o, d) if len(o) else got
    numbers = check.readings(got, ref)
    correct, checks = check.judge(numbers, check.limits(workload, bdir), failed)
    log(f"reference: rays={o.shape[0]} s={time.perf_counter() - t_ref}")
    result = {"correct": correct, "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and ctx["trace"] is not None:
        result["breakdown"] = ctx["trace"]["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    return result

