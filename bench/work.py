"""Operations and bytes per sample of an Instant-NGP field, from the
configuration file's widths (not the program's padded ones).

A marched sample runs the hash encode (8 corners of every level: the
gather and the trilinear blend) and the density MLP; every ``group``-th
sample also runs the color MLP (color-density decoupling).  A probe
sample runs both MLPs.  A matmul of fan-in a and fan-out b is 2ab FLOPs
per row.  The gather reads ``feature_dim`` float32 features per corner;
the MLP weights stay on chip and are not counted.
"""
from __future__ import annotations


def _matmul_flops(sizes) -> int:
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def per_sample(cfg: dict) -> dict:
    g, m, a = cfg["grid"], cfg["mlp"], cfg["asdr"]
    enc = g["n_levels"] * g["feature_dim"]
    dens = [enc] + [m["density_hidden"]] * m["density_layers"] + [1 + m["geo_feature_dim"]]
    col = ([m["geo_feature_dim"] + m["sh_degree"] ** 2] + [m["color_hidden"]] * m["color_layers"]
           + [3])
    density_flops = _matmul_flops(dens)
    color_flops = _matmul_flops(col)
    # trilinear blend: 8 corner weights of 3 factors (2 multiplies), then
    # F multiply-adds per corner, per level
    blend_flops = g["n_levels"] * 8 * (2 + 2 * g["feature_dim"])
    gather_bytes = g["n_levels"] * 8 * g["feature_dim"] * 4
    return {
        "density_flops": density_flops,
        "color_flops": color_flops,
        "march_flops": blend_flops + density_flops + color_flops / a["group"],
        "probe_flops": blend_flops + density_flops + color_flops,
        "gather_bytes": gather_bytes,
    }


def march_least_seconds(cfg: dict, samples: int, peak: dict):
    """Least time the chip could march ``samples`` samples, and its bound:
    (seconds, "bytes" or "flops")."""
    w = per_sample(cfg)
    t_bytes = samples * w["gather_bytes"] / peak["hbm_bytes_per_s"]
    t_flops = samples * w["march_flops"] / peak["bf16_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
