"""Operations and bytes per sample of a nerfacto field, from the
configuration file's widths (not the program's folded or padded ones),
whatever the gather layout.

A proposal sample (Stage A) runs a proposal field: the hash encode of
its grid (8 corners of every level: the gather and the trilinear blend)
and its MLP.  A marched sample runs the main field's encode, mlp_base
and the color head on ``[SH, geo, appearance]`` (every sample's color
runs).  A matmul of fan-in a and fan-out b is 2ab FLOPs a row; the blend
takes 8 corner weights of 3 factors (2 multiplies) and F multiply-adds a
corner, a level.  The gather reads ``feature_dim`` float32 features a
corner; MLP weights stay on chip, and the contraction, the spacing map,
the inverse CDF and compositing are not counted.
"""
from __future__ import annotations

from bench.work import _matmul_flops


def _grid(g: dict):
    """(gather bytes, blend FLOPs) of one point's encode."""
    return (g["n_levels"] * 8 * g["feature_dim"] * 4,
            g["n_levels"] * 8 * (2 + 2 * g["feature_dim"]))


def per_sample(cfg: dict) -> dict:
    g, m, sp = cfg["grid"], cfg["mlp"], cfg["sampler"]
    enc = g["n_levels"] * g["feature_dim"]
    dens = [enc] + [m["density_hidden"]] * m["density_layers"] + [1 + m["geo_feature_dim"]]
    col = ([m["sh_degree"] ** 2 + m["geo_feature_dim"] + m["appearance_embed_dim"]]
           + [m["color_hidden"]] * m["color_layers"] + [3])
    march_bytes, blend = _grid(g)
    n = sp["num_proposal_samples_per_ray"]
    props = []
    for p in cfg["proposal_nets"]:
        b, f = _grid(p)
        sizes = ([p["n_levels"] * p["feature_dim"]] + [p["hidden_dim"]] * (p["num_layers"] - 1)
                 + [1])
        props.append((b, f + _matmul_flops(sizes)))
    rays = sum(n)
    return {
        "march_bytes": march_bytes,
        "march_flops": blend + _matmul_flops(dens) + _matmul_flops(col),
        # a proposal sample, averaged over a ray's samples of both fields
        "proposal_bytes": sum(k * b for k, (b, _f) in zip(n, props)) / rays,
        "proposal_flops": sum(k * f for k, (_b, f) in zip(n, props)) / rays,
        "proposal_samples_per_ray": rays,
    }


def _least(samples, nbytes, flops, peak):
    t_bytes = samples * nbytes / peak["hbm_bytes_per_s"]
    t_flops = samples * flops / peak["bf16_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def march_least_seconds(cfg: dict, samples: int, peak: dict):
    """Least time the chip could march ``samples`` samples, and its bound:
    (seconds, "bytes" or "flops")."""
    w = per_sample(cfg)
    return _least(samples, w["march_bytes"], w["march_flops"], peak)


def proposal_least_seconds(cfg: dict, samples: int, peak: dict):
    """Least time the chip could run ``samples`` proposal samples (a
    ray's mix of both fields), and its bound."""
    w = per_sample(cfg)
    return _least(samples, w["proposal_bytes"], w["proposal_flops"], peak)
