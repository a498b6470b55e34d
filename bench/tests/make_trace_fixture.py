"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/make_trace_fixture.py   # on a machine with a TPU

Inside a ``bench.window`` span: three launches of a jitted matmul, a
host sleep of 50 ms, two more launches.  Writes
``bench/tests/data/tiny.xplane.pb`` and prints each plane's lines.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def tiny(x):
        return jnp.tanh(x @ x) @ x

    x = jnp.ones((2048, 2048), jnp.float32)
    tiny(x).block_until_ready()
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            tiny(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host.sleep"):
            time.sleep(0.05)
        for _ in range(2):
            tiny(x).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(Path(tdir).rglob("*.xplane.pb"))[-1]
    OUT.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, OUT)
    shutil.rmtree(tdir)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(str(OUT)).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(plane.name, lines)
        for ln in plane.lines:
            for e in list(ln.events)[:4]:
                print("   ", ln.name, "|", e.name, e.start_ns, e.duration_ns)
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
