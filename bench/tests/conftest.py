"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
repository root (the root ``pytest.ini`` collects only ``tests/``)."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


SMOKE = {  # configs/ingp_asdr.SMOKE's sizes
    "grid": {"n_levels": 8, "log2_table_size": 14, "feature_dim": 2,
             "base_resolution": 16, "max_resolution": 256},
    "mlp": {"density_hidden": 64, "density_layers": 1, "geo_feature_dim": 15,
            "sh_degree": 4, "color_hidden": 64, "color_layers": 2},
    "asdr": {"ns_full": 64, "probe_stride": 4, "delta": 0.00048828125,
             "candidates": [8, 16, 32], "group": 2, "block_size": 64, "chunk": 16,
             "early_termination": True, "white_background": True},
}


def smoke_root(tmp: Path) -> Path:
    """A copy of the benchmark with two cells at SMOKE size on 16x16
    frames, ``smoke.fresh`` and ``smoke.session``, holding the limits of
    ``ingp-paper.fresh-128`` and ``ingp-paper.session-64``."""
    import json
    import shutil
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    b = root / "bench"
    cfg = json.loads((b / "configs" / "ingp-paper.json").read_text())
    cfg.update(SMOKE, name="smoke")
    (b / "configs" / "smoke.json").write_text(json.dumps(cfg))
    cells = []
    for cell, mix, src in (("smoke.fresh", "fresh-tiny", "ingp-paper.fresh-128"),
                           ("smoke.session", "session-tiny", "ingp-paper.session-64")):
        t = json.loads((b / "traffic" / f"{src.split('.')[1]}.json").read_text())
        t.update(frame=[16, 16], check_pixels_per_frame=64)
        if t["loop"]["kind"] == "open":
            t["loop"]["rate_per_s"] = 4.0
        (b / "traffic" / f"{mix}.json").write_text(json.dumps(t))
        shutil.copy(b / "limits" / f"{src}.json", b / "limits" / f"{cell}.json")
        cells.append((cell, mix, src))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "smoke", "source": "https://arxiv.org/abs/2508.02304",
                            "file": "bench/configs/smoke.json", "reduced": [], "why": "CPU"})
    for cell, mix, src in cells:
        spec["workloads"].append({"name": cell, "config": "smoke", "traffic": mix,
                                  "chips": 1, "why": "CPU rehearsal"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if src in m.get("workloads", []):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
