"""The harness end to end on the CPU at SMOKE size: no result line without
a chip; a sound run is correct; the control and each fault the cells can
have are not; a new configuration, traffic mix and metric need only new
files."""
import hashlib
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT, smoke_root

from bench import check, harness
from bench.reference import ingp as reference

SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke_root(tmp_path_factory.mktemp("bench"))


def run(root, workload="smoke.fresh", seconds=2.0, trace=False):
    return harness.run(workload, SEED, seconds, trace, time.perf_counter(),
                       require_chip=False, root=root)


def test_no_chip_no_result_line(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cwd in (ROOT, root):
        p = subprocess.run([sys.executable, "bench/run.py", "--workload", "smoke.fresh"
                            if cwd == root else "ingp-paper.fresh-128", "--seed", "5",
                            "--seconds", "1", "--trace", "0"],
                           cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
        assert p.returncode != 0
        assert "{" not in p.stdout


@pytest.mark.parametrize("workload", ["smoke.fresh", "smoke.session"])
def test_sound_run_is_correct(root, workload):
    r = run(root, workload, trace=workload.endswith("session"))
    assert r["correct"] and r["failed"] == 0, r["checks"]
    assert list(r)[-1] == "checks"
    names = set(r["metrics"])
    if workload.endswith("fresh"):
        assert names == {"rays_per_s", "setup_s"}
    else:
        assert {"probe_reuse_fraction.session", "pad_block_fraction.session",
                "admit_stall_p90_ms.session"} <= names


def test_control_is_not_correct(root):
    """The reference in bfloat16 against float32, on the pixels a run
    compares, fails the cell's limits."""
    from bench import traffic
    cfg = json.loads((root / "bench" / "configs" / "smoke.json").read_text())
    spec = traffic.load("fresh-tiny", root / "bench")
    params = reference.make_params(cfg, SEED)
    frames = [(rid, cam, np.zeros((16, 16, 3))) for rid, cam, _ in
              traffic.requests(spec, SEED, 0, 16)]
    o, d, _ = check.rays(reference, frames, SEED, 64)
    f32 = reference.render(cfg, params, o, d)
    low = reference.render(cfg, params, o, d, dtype=jnp.bfloat16)
    ok, checks = check.judge(check.readings(low, f32),
                             check.limits("smoke.fresh", root / "bench"), 0)
    assert not ok, checks


def _half_batch(orig):
    def march(*args):
        rgb, acc, depth, chunks, ray_chunks = orig(*args)
        h = rgb.shape[0] // 2
        # the second half of the batch left out: the mean of the rest
        rgb = rgb.at[h:].set(jnp.mean(rgb[:h], axis=0))
        return rgb, acc, depth, chunks, ray_chunks
    return march


def _unchanged(orig):
    def march(*args):
        rgb, acc, depth, chunks, ray_chunks = orig(*args)
        # the state the march starts from: nothing composited, white
        return jnp.ones_like(rgb), jnp.zeros_like(acc), depth, chunks * 0, ray_chunks
    return march


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged", "answer_altered"])
def test_fault_is_not_correct(root, monkeypatch, fault):
    from repro.serve import admission, pool
    if fault == "answer_altered":
        orig = admission.Slot.finalize

        def finalize(self, acfg):
            req = orig(self, acfg)
            if req.rid == 1:       # one frame altered where it is produced
                req.image = req.image + 0.05
            return req
        monkeypatch.setattr(admission.Slot, "finalize", finalize)
    else:
        wrap = {"half_batch": _half_batch, "state_unchanged": _unchanged}[fault]
        monkeypatch.setattr(pool, "_march", wrap(pool._march))
        monkeypatch.setattr(pool, "_MARCH_CACHE", type(pool._MARCH_CACHE)())
    r = run(root)
    assert not r["correct"], r["checks"]


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_make_a_new_cell(root, tmp_path):
    """A configuration, a traffic mix, a metric and limits dropped in as new
    files, plus entries in BENCHMARK.json, run with no other file edited."""
    import shutil
    copy = tmp_path / "checkout"
    shutil.copytree(root, copy)
    before = _digests(copy)
    b = copy / "bench"
    cfg = json.loads((b / "configs" / "smoke.json").read_text())
    cfg["mlp"]["color_hidden"] = 32
    (b / "configs" / "smoke-narrow.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "fresh-tiny.json").read_text())
    mix["frame"] = [8, 32]
    (b / "traffic" / "wide-tiny.json").write_text(json.dumps(mix))
    (b / "metrics" / "frames_served.extra.py").write_text(
        "def read(ctx):\n    return float(len(ctx['served']))\n")
    shutil.copy(b / "limits" / "smoke.fresh.json", b / "limits" / "smoke-narrow.wide.json")
    spec = json.loads((copy / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "smoke-narrow", "source": "https://arxiv.org/abs/2201.05989",
                            "file": "bench/configs/smoke-narrow.json", "reduced": [],
                            "why": "x"})
    spec["workloads"].append({"name": "smoke-narrow.wide", "config": "smoke-narrow",
                              "traffic": "wide-tiny", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "frames_served.extra", "unit": "frames",
                              "better": "higher", "source": "program_counter",
                              "layer": "engine and scheduler", "moves": "rays_per_s",
                              "workloads": ["smoke-narrow.wide"]})
    spec["end_to_end"][0]["workloads"].append("smoke-narrow.wide")
    (copy / "BENCHMARK.json").write_text(json.dumps(spec))
    code = ("import sys, time, json; sys.path[:0] = [sys.argv[1], sys.argv[2]]\n"
            "from bench import harness\n"
            "r = harness.run('smoke-narrow.wide', 3, 2.0, True, time.perf_counter(),"
            " require_chip=False, root=harness.ROOT)\n"
            "print(json.dumps(r))\n")
    p = subprocess.run([sys.executable, "-c", code, str(copy), str(ROOT / "src")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["metrics"]["frames_served.extra"]["value"] == r["attempted"] > 0
    assert r["correct"], r["checks"]
    after = _digests(copy)
    assert all(after[p] == h for p, h in before.items())
