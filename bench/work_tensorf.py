"""Operations and bytes per sample of a TensoRF VM field, from the
configuration file's widths.

A point reads, on each side it runs, 4 plane rows and 2 line rows of each
of the 3 plane/line pairs: 18 rows of R float32 components (64 B for the
16 density components, 192 B for the 48 appearance ones).  A marched
sample runs the density side; every ``group``-th also runs the color
side (color-density decoupling), as every probe sample does.  FLOPs: a
multiply-add is 2, a lone multiply or add 1, a matmul of fan-in a and
fan-out b 2ab a row; transcendental functions (softplus, sin, cos,
sigmoid) are not counted, and the weights stay on chip.
"""
from __future__ import annotations

ROWS = 3 * 4 + 3 * 2


def _side_flops(R: int) -> int:
    # a pair: 4 bilinear weights (1 multiply each) and 4 rows of R
    # multiply-adds on the plane, 2 rows of R multiply-adds on the line,
    # then R products
    return 3 * (4 + 4 * 2 * R + 2 * 2 * R + R)


def per_sample(cfg: dict) -> dict:
    t, a = cfg["tensorf"], cfg["asdr"]
    rs, rc, F, H = t["n_lamb_sigma"], t["n_lamb_sh"], t["data_dim_color"], t["featureC"]
    n_in = F + 3 + 2 * t["fea_pe"] * F + 2 * t["view_pe"] * 3
    mlp = [n_in, H, H, 3]
    density_flops = _side_flops(rs) + 3 * rs          # + the sum over components
    color_flops = (_side_flops(rc) + 2 * 3 * rc * F
                   + sum(2 * i * o + o for i, o in zip(mlp[:-1], mlp[1:]))
                   + F * t["fea_pe"] + 3 * t["view_pe"])         # PE's scalings
    density_bytes, color_bytes = ROWS * rs * 4, ROWS * rc * 4
    return {
        "density_flops": density_flops,
        "color_flops": color_flops,
        "march_flops": density_flops + color_flops / a["group"],
        "probe_flops": density_flops + color_flops,
        "march_rows": ROWS + ROWS / a["group"],
        "gather_bytes": density_bytes + color_bytes / a["group"],
    }


def march_least_seconds(cfg: dict, samples: int, peak: dict):
    """Least time the chip could march ``samples`` samples, and its bound:
    (seconds, "bytes" or "flops")."""
    w = per_sample(cfg)
    t_bytes = samples * w["gather_bytes"] / peak["hbm_bytes_per_s"]
    t_flops = samples * w["march_flops"] / peak["bf16_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
