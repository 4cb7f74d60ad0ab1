"""Production mesh construction (multi-pod dry-run requirement).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state.  The single-pod mesh is
16 x 16 = 256 chips (data, model); the multi-pod mesh is 2 x 16 x 16 = 512
chips (pod, data, model) — the ``pod`` axis is the solver's outer steal
ring.
"""

from __future__ import annotations

import jax

from repro import peaks


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


# Per-chip peaks of the production chip used by the roofline analysis —
# read from the one peak table, ``repro.peaks`` (keyed by device_kind).
_V5E = peaks.peaks_for("TPU v5 lite")
PEAK_FLOPS_BF16 = _V5E.bf16_flops
HBM_BW = _V5E.hbm_bytes_per_s
ICI_BW = _V5E.ici_bytes_per_s
