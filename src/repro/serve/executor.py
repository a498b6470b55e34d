"""Pluggable Stage-A execution backends for the serving pipeline.

The executor contract (see serve/README.md):

  * ``submit(key, fn)`` — schedule ``fn()`` (a Stage-A ``prepare``
    closure: plans + probe/warp device work + pad/sort layout) for
    ``key``.  Idempotent: a key already submitted and not yet taken is
    NOT resubmitted.  Raises RuntimeError after ``close()``.
  * ``take(key)`` — the finished result, blocking if still in flight;
    None if the key was never submitted (the engine then prepares
    inline).  Engine thread only.  Every submitted key must eventually
    be taken or reset — ``pending()`` counts what hasn't been (the leak
    check in tests/test_executor.py).
  * ``reset()`` — drop pending speculation (end of a render() call).
    Idempotent.
  * ``close()`` — release worker resources.  Idempotent; the executor
    rejects new submissions afterwards.

Backends move WHERE and WHEN the speculation executes; they never change
WHAT is committed — Stage B revalidates every plan against current cache
state on the engine thread, so rendered frames and the deterministic
counters are bit-identical across backends (gated by
tests/test_executor.py, tests/test_fleet.py, and the ``--workers`` /
fleet benchmarks).

``SyncExecutor`` (the default) runs ``fn`` inline at submit time on the
engine thread — byte-for-byte the pre-executor engine: the speculation
overlaps only the HOST-side gap while the dispatched round — up to
``inflight_batches`` back-to-back march batches (pool.dispatch_round) —
is in flight.
``ThreadedExecutor`` runs it on a worker pool and blocks each worker
until the result's device buffers are READY, so probe/warp device time
genuinely overlaps march device time.  ``DeviceExecutor`` additionally
PLACES each speculation on a secondary jax device (round-robin over
``jax.devices()[1:]``) while the pooled march keeps device 0 — the
scale-out placement the fleet tier runs on (multi-device CI forces host
devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=K``).
"""
from __future__ import annotations

import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

import jax

from ..obs import trace as trace_lib


def _available_devices() -> List:
    """The jax device list (module hook so tests can model single- and
    multi-device hosts without touching global jax state)."""
    return jax.devices()


class SyncExecutor:
    """Inline (engine-thread) Stage-A execution — the default backend."""

    workers = 0
    backend = "sync"

    def __init__(self):
        self._done: Dict = {}
        self._closed = False

    def submit(self, key, fn: Callable):
        if self._closed:
            raise RuntimeError("submit() on a closed executor")
        if key not in self._done:
            # sync backend runs the closure AT submit — the span covers
            # the actual Stage-A execution on the engine lane
            with trace_lib.span("executor.submit", backend=self.backend):
                self._done[key] = fn()

    def take(self, key):
        return self._done.pop(key, None)

    def pending(self) -> int:
        """Submitted-but-not-taken keys (0 after a clean render())."""
        return len(self._done)

    def depth(self) -> Dict[str, int]:
        """Queue-depth gauge sample (scheduler stall projections read
        this through the metrics registry).  Sync results are complete
        at submit, so nothing is ever in flight."""
        return {"pending": len(self._done), "inflight": 0}

    def reset(self):
        """Drop pending speculation (end of a render() call): results are
        keyed by id(request), and a key must never outlive the call that
        submitted it — a later call's request can reuse the id."""
        self._done.clear()

    def close(self):
        self._done.clear()
        self._closed = True


class _FutureExecutor:
    """Shared future-backed machinery for the off-thread backends.

    Subclasses provide ``_spawn(key, fn) -> Future``.  ``take`` WORK-
    STEALS: a speculation still queued (its future never started) is
    cancelled and run inline on the engine thread instead of waiting for
    a busy worker — the engine must never stall behind speculation it
    could execute itself (the threaded-stall-p99 regression fix; see
    tests/test_executor.py::test_take_steals_queued_speculation).
    """

    def __init__(self):
        self._futs: Dict[object, Tuple[Future, Callable]] = {}
        self._closed = False

    def _spawn(self, key, fn: Callable) -> Future:
        raise NotImplementedError

    def submit(self, key, fn: Callable):
        if self._closed:
            raise RuntimeError("submit() on a closed executor")
        if key not in self._futs:
            self._futs[key] = (self._spawn(key, fn), fn)

    backend = "future"

    def take(self, key):
        ent = self._futs.pop(key, None)
        if ent is None:
            return None
        fut, fn = ent
        if fut.cancel():          # never started: steal it inline
            with trace_lib.span("executor.take", backend=self.backend,
                                stolen=True):
                return fn()
        # the span covers the engine-side WAIT for a busy worker — on an
        # idle executor it closes immediately; long takes here mean
        # speculation is not keeping ahead of admission
        with trace_lib.span("executor.take", backend=self.backend,
                            stolen=False):
            return fut.result()

    def pending(self) -> int:
        return len(self._futs)

    def depth(self) -> Dict[str, int]:
        """Queue-depth gauge sample: ``pending`` = submitted-not-taken
        speculations, ``inflight`` = the subset actually EXECUTING on a
        worker/device right now (the rest are queued behind the
        concurrency cap — a growing pending/inflight gap means
        speculation is falling behind admission)."""
        running = sum(1 for fut, _fn in self._futs.values()
                      if fut.running())
        return {"pending": len(self._futs), "inflight": running}

    def reset(self):
        """Drop pending speculation (see SyncExecutor.reset).  Unstarted
        futures are cancelled; running ones finish on their worker and
        are discarded.  Idempotent."""
        for fut, _fn in self._futs.values():
            fut.cancel()
        self._futs.clear()

    def close(self):
        self.reset()
        self._closed = True


def _wait_device_ready(out):
    ready = getattr(out, "block_until_ready", None)
    if ready is not None:
        ready()


class ThreadedExecutor(_FutureExecutor):
    """Worker-thread Stage-A execution.

    Workers run the prepare closure AND wait on its device buffers
    (``block_until_ready``), so the device work completes off the engine
    thread.  Commits still happen only on the engine thread in admission
    order — ``take`` blocks until the worker finishes (or steals a
    still-queued closure inline), and Stage B revalidates the result, so
    worker scheduling can never reorder or alter commits.

    ``max_concurrent`` bounds how many speculations EXECUTE at once
    (queued submissions wait on a semaphore, FIFO): worker count is an
    API/capacity property, but useful execution concurrency is a HOST
    property — on a 2-core CPU container, four concurrent probe/warp
    executions would fight the in-flight march (and each other) for the
    same ALUs and triple tail latency instead of hiding it.  The default
    leaves one core's worth of concurrency for the engine thread + march.
    On a multi-stream accelerator host, pass workers explicitly sized to
    the streams and the cap follows.
    """

    backend = "threaded"

    def __init__(self, workers: int, max_concurrent: Optional[int] = None):
        super().__init__()
        assert workers > 0
        self.workers = workers
        if max_concurrent is None:
            max_concurrent = min(workers,
                                 max(1, (os.cpu_count() or 2) - 1))
        self.max_concurrent = max_concurrent
        self._sem = threading.Semaphore(max_concurrent)
        self._pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="serve-stage-a")

    def _run(self, fn: Callable):
        with self._sem:
            # recorded on the worker's own lane (thread name) — the
            # speculation that overlaps the in-flight march
            with trace_lib.span("executor.run", backend=self.backend):
                out = fn()
                _wait_device_ready(out)
        return out

    def _spawn(self, key, fn: Callable) -> Future:
        return self._pool.submit(self._run, fn)

    def close(self):
        super().close()
        self._pool.shutdown(wait=False)


class DeviceExecutor(_FutureExecutor):
    """Multi-device Stage-A execution: speculation on secondary devices.

    Placement rule (the fleet contract, serve/README.md): the pooled
    march owns device 0 — Stage-A probe/warp closures are placed on the
    SECONDARY devices (``jax.devices()[1:]`` by default), round-robin
    per submitted slot, each device backed by its own single-thread
    queue (the host-side stand-in for a per-device stream).  The closure
    runs under ``jax.default_device(dev)``, so its jitted probe/warp
    computations compile and execute on that device; its result arrays
    transfer to device 0 implicitly when the commit path consumes them.
    A neural field's params enter the jitted probe as arguments
    (``fields.unbind``); built by ordinary jnp ops they are uncommitted,
    so each probe copies them to ``dev`` (params committed to one device
    with ``jax.device_put`` would pin the probe there).

    Determinism: host platform devices share one codegen, so a probe
    executed on device k is bit-identical to the same probe on device 0
    — and on hosts where that may not hold, Stage-B revalidation still
    bounds the blast radius to the speculated maps a commit chose to
    reuse.  tests/test_fleet.py gates frames and deterministic counters
    against the SyncExecutor for devices {1, 2, 4} x prefetch {0, 2}
    under ``--xla_force_host_platform_device_count=4``.

    A stolen ``take`` (speculation still queued when the engine needs
    it) runs inline on the engine thread / device 0, exactly like the
    sync backend — placement is best-effort under load, never a stall.
    """

    backend = "device"

    def __init__(self, devices: Optional[List] = None):
        super().__init__()
        if devices is None:
            devices = _available_devices()[1:]
        assert devices, "DeviceExecutor needs at least one device"
        self.devices = list(devices)
        self.workers = len(self.devices)
        self._pools = [
            ThreadPoolExecutor(max_workers=1,
                               thread_name_prefix=f"serve-dev{i}")
            for i in range(len(self.devices))]
        self._rr = 0
        # closures executed per secondary device (a stolen take runs
        # inline and is not counted); each slot is written only by its
        # device's single queue thread
        self.runs = [0] * len(self.devices)

    def _run(self, i, fn: Callable):
        dev = self.devices[i]
        self.runs[i] += 1
        with jax.default_device(dev):
            # device attr records the PLACEMENT; the lane (serve-dev*)
            # records the per-device queue that executed it
            with trace_lib.span("executor.run", backend=self.backend,
                                device=str(dev)):
                out = fn()
                _wait_device_ready(out)
        return out

    def _spawn(self, key, fn: Callable) -> Future:
        i = self._rr % len(self.devices)
        self._rr += 1
        return self._pools[i].submit(self._run, i, fn)

    def close(self):
        super().close()
        for pool in self._pools:
            pool.shutdown(wait=False)


def make_executor(workers: int, devices: int = 0):
    """The backend for a (workers, devices) config.

    ``devices=n > 0`` asks for Stage-A placement on up to n secondary
    jax devices.  Graceful fallback: a single-device host has no
    secondary device to place on, so the config degrades to the
    bit-identical SyncExecutor instead of failing — the same binary
    serves a laptop and a fleet host (tests/test_executor.py and
    tests/test_fleet.py cover both sides).  Otherwise ``workers=n > 0``
    selects the ThreadedExecutor; the default is synchronous.
    """
    if devices > 0:
        avail = _available_devices()
        if len(avail) > 1:
            return DeviceExecutor(avail[1:1 + devices])
        return SyncExecutor()
    return ThreadedExecutor(workers) if workers > 0 else SyncExecutor()


def block_until_ready(*arrays):
    """Wait until every (possibly-None, possibly-host) array is ready."""
    jax.block_until_ready([a for a in arrays if a is not None])
