"""Pytest config.

XLA_FLAGS: the device COUNT is deliberately left alone (smoke tests and
benches must see 1 device; multi-device tests spawn subprocesses that set
their own count) — but the fast tier DOES append
``--xla_backend_optimization_level=0`` below, and child processes inherit
it unless they overwrite XLA_FLAGS (the subprocess tests do).
"""
import os


# Cheap XLA backend codegen for the fast tier (~20% less compile time on
# CPU; numerics unchanged — the full suite passes either way).  Device
# count is deliberately untouched (see module docstring).  Opt out with
# REPRO_FAST_TESTS=0.  Must run before the first jax import, which is why
# it lives at conftest import time and not in a fixture.
if os.environ.get("REPRO_FAST_TESTS", "1") != "0":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_backend_optimization_level" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_backend_optimization_level=0").strip()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
