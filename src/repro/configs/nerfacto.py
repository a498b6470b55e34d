"""nerfacto (arXiv:2302.04264) served through the render engine.

``CONFIG`` is nerfstudio's ``NerfactoModelConfig`` at its defaults: a
16-level 2^19 x 2 hash grid (resolution 16 .. 2048) under mlp_base
32 -> 64 -> 16, a color head 63 -> 64 -> 64 -> 3 fed a 32-wide
appearance embedding, two proposal fields of 5 levels at 2^17 x 2 (max
resolution 128 and 256) under 10 -> 16 -> 1, and 256 then 96 proposal
samples before 48 marched ones.  The march takes every sample's color
(group 1) in chunks of 16 (a 16-block batch marched 1.308 s against
1.421 s in one chunk of 48 on one v5e), with no early termination
(nerfstudio has none); the engine serves it with ingp-paper's 4 slots,
16-block batches and prefetch 2, and no probe reuse (the field brings
its own sampler).  ``SMOKE`` is the CPU-sized reduction used by tests,
with a march whose last chunk is cut short.
"""
import dataclasses

from repro.core import hashgrid, mlp
from repro.core.nerfacto import NerfactoConfig
from repro.core.pipeline import ASDRConfig


@dataclasses.dataclass(frozen=True)
class NerfactoBundle:
    name: str
    model: NerfactoConfig
    asdr: ASDRConfig


CONFIG = NerfactoBundle(
    name="nerfacto",
    model=NerfactoConfig(),
    asdr=ASDRConfig(ns_full=48, candidates=(48,), group=1, block_size=4096,
                    chunk=16, early_termination=False),
)


def _smoke_grid(levels, log2, max_res):
    return hashgrid.HashGridConfig(n_levels=levels, log2_table_size=log2,
                                   max_resolution=max_res)


SMOKE = NerfactoBundle(
    name="nerfacto-smoke",
    model=NerfactoConfig(
        grid=_smoke_grid(8, 14, 256),
        net=mlp.MLPConfig(encoding_dim=16),
        proposal_grids=(_smoke_grid(3, 12, 48), _smoke_grid(3, 12, 64)),
        samples=(64, 24, 12)),
    asdr=ASDRConfig(ns_full=12, candidates=(12,), group=1, block_size=64,
                    chunk=8, early_termination=False),
)
