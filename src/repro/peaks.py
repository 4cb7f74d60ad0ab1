"""Per-chip peak rates, keyed by ``jax.Device.device_kind``.

The one table every consumer reads: the kernel autotuner
(``repro.kernels.autotune``) and the roofline dry-runs
(``repro.launch.mesh``).  A device kind that is not here is an error, not
a default — a model scaled by another chip's peaks is wrong silently.

Published figures come from the vendor's documentation, named in
``source``.  The last three fields are ESTIMATES, not published: they only
rank kernel layouts against each other and are never reported as a
device's speed.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Peak rates of one chip."""

    bf16_flops: float          # published: bf16 FLOP/s
    int8_ops: float            # published: int8 OP/s
    hbm_bytes_per_s: float     # published: HBM bandwidth
    hbm_bytes: float           # published: HBM capacity
    ici_bytes_per_s: float     # published: chip-to-chip interconnect
    source: str
    word_ops_per_s: float      # estimate: uint32 VPU word-ops/s
    vmem_budget_bytes: int     # estimate: VMEM one kernel grid step may use
    grid_step_s: float         # estimate: overhead of one kernel grid step


PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        ici_bytes_per_s=1600e9 / 8,          # 1,600 Gbit/s
        source='Google Cloud documentation, "TPU v5e" (system '
               'architecture: per-chip peak compute, HBM, ICI)',
        word_ops_per_s=4e12,
        vmem_budget_bytes=4 * 1024 * 1024,
        grid_step_s=2e-7,
    ),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    """The table entry for ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak-table entry for device kind {device_kind!r} (known: "
            f"{', '.join(sorted(PEAKS))}); add its published figures to "
            f"repro.peaks.PEAKS") from None
