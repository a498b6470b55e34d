# Tier-1 verification targets.  `make test` is the CI entry point: the
# fast subset (slow train/e2e tests excluded via pytest.ini addopts),
# bounded well under 120 s on this container.
PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-fleet test-full lint bench-serve bench-serve-sweep \
        bench-serve-latency bench-serve-workers bench-obs \
        bench-scenecache bench-scenecache-budgets bench-fleet \
        bench-slo dryrun-serve

test:
	$(PY) -m pytest -x -q

# multi-device fleet lane: jax locks the device count at init, so these
# tests need their own interpreter with forced host devices (cheap CPU
# stand-in for a multi-chip host; see tests/test_fleet.py)
test-fleet:
	XLA_FLAGS="--xla_force_host_platform_device_count=4$(if $(XLA_FLAGS), $(XLA_FLAGS))" \
	$(PY) -m pytest -x -q -m fleet

test-full:
	$(PY) -m pytest -m "" -q

# ruff > pyflakes > the ast-based fallback in tools/lint.py (this
# container bakes in neither linter; CI installs ruff), plus the
# file-size budget check (the serve facade must stay a thin loop) and
# the trace-format self-test (exporter -> validator round trip)
lint:
	$(PY) tools/lint.py src tests benchmarks examples tools
	$(PY) tools/check_sizes.py
	$(PY) tools/check_trace.py

bench-serve:
	$(PY) benchmarks/render_serve.py

bench-serve-sweep:
	$(PY) benchmarks/render_serve.py --sweep

bench-serve-latency:
	$(PY) benchmarks/render_serve.py --latency

bench-serve-workers:
	$(PY) benchmarks/render_serve.py --workers

# tracing-overhead gate: tracer on must cost <= 5% fps at 0.0 dB delta
bench-obs:
	$(PY) benchmarks/render_serve.py --obs

bench-scenecache:
	$(PY) benchmarks/scene_cache.py

bench-scenecache-budgets:
	$(PY) benchmarks/scene_cache.py --budgets

# SLO gate: open-loop Poisson overload — at the deepest factor
# ShedPolicy must hold rt-class p99 under the FIFO baseline with
# sheds > 0; lighter factors gate non-regression only (smoke = one
# factor, best-of-2; drop --smoke for the full 0.7/1.5/2.5x sweep)
bench-slo:
	$(PY) benchmarks/render_serve.py --slo --smoke

# N engine replicas x one shared sharded scenecache (the script forces
# 4 host devices itself when XLA_FLAGS doesn't already pin a count)
bench-fleet:
	$(PY) benchmarks/render_fleet.py

dryrun-serve:
	$(PY) -m repro.launch.render_serve --dryrun
