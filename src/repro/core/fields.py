"""Field abstraction — the composability seam of the renderer.

Everything downstream of sampling (decoupling, adaptive probing, the
two-phase ASDR pipeline and its chunked march) consumes a ``FieldFns``
pair instead of a concrete model, so the same pipeline runs

  * the trained Instant-NGP network      (``model.field_fns``),
  * the exact analytic scene field       (``analytic_field_fns``) — used by
    tests to validate the *algorithm* independently of training error,
  * Instant-NGP on the Pallas MLP kernels (``kernels.ops.field_fns``),
  * TensoRF's VM-decomposed field          (``tensorf.field_fns``),
  * nerfacto's contracted field with its proposal sampler
                                           (``nerfacto.field_fns``).

``density(points) -> (sigma (N,), geo (N, G))`` and
``color(geo, dirs) -> rgb (N, 3)``.  ``geo`` is whatever per-point input
the color side needs, handed over unchanged (and only at the samples
whose color runs, under decoupling): Instant-NGP's geometry feature, the
ground-truth color of an analytic field (``color`` is then a
projection), or TensoRF's sample point itself, whose appearance tables
``color`` reads.

A field may bring its own ``sampler``, which Stage A runs over every
pixel ray in place of ASDR's probe: ``sampler.edges(origins, dirs)``
gives each ray's interval edges ``(R, n + 1)`` in the sampler's spacing
space (ascending in [0, 1]), ``sampler.ts(edges)`` maps them to program
distances along the ray, and ``sampler.samples_per_ray`` counts the
field evaluations ``edges`` makes a ray.  The march then takes its
samples at those intervals' midpoints, each as wide as its interval,
instead of ASDR's uniform ones (``pipeline.march_blocks``).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple

import jax.numpy as jnp


class FieldFns(NamedTuple):
    """A field's two closures and, optionally, the arrays they capture."""
    density: Callable  # (N,3) -> (sigma (N,), geo (N,G): any per-point input of color)
    color: Callable    # (geo (N,G), dirs (N,3)) -> rgb (N,3)
    # Optional ``Bound``: the arrays the closures above capture, and how
    # to rebuild the closures around any value of them.  Jitted callers
    # use it (``unbind``/``rebind``) to pass the params as ARGUMENTS; a
    # closure over concrete arrays would bake them into the executable
    # as constants (64 MB of hash tables at the paper config).
    bound: object = None
    # Optional ``Sampler``: the field places its own samples, and Stage A
    # runs it in place of ASDR's probe.
    sampler: object = None


class Sampler(NamedTuple):
    """A field's own sampler (see the module docstring)."""
    edges: Callable          # (origins (R,3), dirs (R,3)) -> (R, S+1) in [0, 1]
    ts: Callable             # spacing-space edges -> program distances
    samples_per_ray: int     # field evaluations ``edges`` makes a ray


class Bound:
    """``build(params, cfg)`` rebuilds the FieldFns that holds this.

    A plain class (identity hash) so a FieldFns carrying one stays
    hashable; ``unbind`` keys jitted code on ``(build, cfg)`` only, so
    fields that differ only in their params share one executable.  A
    ``cfg`` with a ``gather_stats(params)`` method reports how the field
    gathers its tables (``gather_stats`` below).
    """

    def __init__(self, build: Callable, cfg, params):
        self.build = build
        self.cfg = cfg
        self.params = params


def unbind(fns: FieldFns):
    """Split ``fns`` into (static key, params): the key is hashable and
    holds no arrays, the params are what a jitted caller passes as
    arguments; ``rebind(key, params)`` inside the trace gives back the
    field.  Fields without ``bound`` (analytic ones hold no arrays) are
    their own key."""
    if fns.bound is None:
        return fns, None
    return (fns.bound.build, fns.bound.cfg), fns.bound.params


def rebind(key, params) -> FieldFns:
    """Inverse of ``unbind``."""
    if isinstance(key, FieldFns):
        return key
    build, cfg = key
    return build(params, cfg)


def gather_stats(fns: FieldFns) -> Dict[str, int]:
    """The table rows the field gathers a point, as its config reports
    them: ``encode_gather_rows_per_sample`` (density side, every marched
    sample), ``color_gather_rows_per_sample`` (color side, every point
    its color runs) and any counts of the field's own.  Empty for a field
    that reads no tables."""
    b = fns.bound
    report = getattr(b.cfg, "gather_stats", None) if b is not None else None
    return report(b.params) if report is not None else {}


def analytic_field_fns(field) -> FieldFns:
    """Wrap an analytic ``scene.Field`` (points -> (sigma, color))."""

    def density(points):
        sigma, color = field(points)
        inside = jnp.all((points >= 0.0) & (points <= 1.0), axis=-1)
        return jnp.where(inside, sigma, 0.0), color

    def color(geo, dirs):
        del dirs
        return geo

    return FieldFns(density=density, color=color)
