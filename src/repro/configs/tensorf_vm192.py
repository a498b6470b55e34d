"""TensoRF VM-192 (arXiv:2203.09517) under ASDR's two-phase rendering.

``CONFIG`` is the published NeRF-Synthetic setting (the authors'
``configs/lego.txt`` with the ``opt.py`` defaults): a 300^3 grid, 16
density and 48 appearance components a plane/line pair, 27 features,
the 150-128-128-3 feature MLP, and half-voxel steps, which over the
program's ray span [0.2, 2.2] of a unit cube that is 3 world units wide
make ``ns_full`` = 2 / (0.5 * 3/299 / 3) = 1196.  Every other ASDR and
serving setting is ``configs/ingp_asdr.CONFIG``'s; the candidate counts
are 1/16 .. 1/2 of ``ns_full``, the ratios of its 12 .. 96 of 192.
``SMOKE`` is the CPU-sized reduction used by tests, with a last chunk
that is cut short (``ns_full % chunk != 0``) as at CONFIG.
"""
import dataclasses

from repro.core.pipeline import ASDRConfig
from repro.core.tensorf import TensoRFConfig


@dataclasses.dataclass(frozen=True)
class TensoRFBundle:
    name: str
    model: TensoRFConfig
    asdr: ASDRConfig


CONFIG = TensoRFBundle(
    name="tensorf-vm192",
    model=TensoRFConfig(),
    asdr=ASDRConfig(ns_full=1196, probe_stride=5, delta=1.0 / 2048.0,
                    candidates=(75, 150, 299, 598), group=2,
                    block_size=4096, chunk=32),
)

SMOKE = TensoRFBundle(
    name="tensorf-vm192-smoke",
    model=TensoRFConfig(grid=24, density_components=4,
                        appearance_components=6, feature_dim=5, hidden=16),
    asdr=ASDRConfig(ns_full=60, probe_stride=4, delta=1.0 / 2048.0,
                    candidates=(8, 15, 30), group=2, block_size=64,
                    chunk=16),
)
