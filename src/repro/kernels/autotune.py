"""Block-shape autotuner for the bitset kernels (DESIGN.md §5.6).

The masked-popcount kernels (``bitset_ops.count_stats`` and friends) are
parameterized by a vertex ``tile`` (rows of the table DMA'd per grid step)
and a ``stages`` mode (1 = the sequential-accumulate grid, 2 = the
split-phase partial/combine layout of DESIGN.md §5.5).  The right choice
depends on the problem shape ``(n, w, L, K)`` and where the kernel runs:

  * compiled TPU — the ``[tile, L]`` per-block intermediates must fit in
    VMEM next to the table block and the lane masks, and within that
    budget fewer, larger grid steps amortize DMA issue.  The rates and the
    budget come from the chip's entry in ``repro.peaks`` (keyed by
    ``device_kind``; an unknown TPU raises);
  * interpret (the CPU platform) — every grid step is a Python-level
    iteration of the interpreter's scan, so per-step overhead dominates by
    orders of magnitude and the winner is simply the fewest grid steps.

Rather than hand-tuning per call site, :func:`choose` scores every
power-of-two candidate with an analytic cost model built from
``repro.roofline.RooflineCounts.terms`` plus a per-grid-step overhead, and
caches the winner per shape and device.  :func:`measured_choice` is the
optional measured sweep: it times the real kernel on synthetic operands
and overrides the analytic pick in the same cache.

The other per-platform kernel decision lives here too:
:func:`resolve_interpret` runs the Pallas interpreter only on the CPU
platform (or on request).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax

from repro import peaks
from repro.roofline import RooflineCounts

#: The interpreter runs a grid step as one host-level scan iteration —
#: measured O(10µs) per step on CPU.  Its roofline terms and VMEM budget
#: borrow this chip's entry, so a CPU choice is also feasible on the chip.
INTERPRET_STEP_S = 1.5e-5
INTERPRET_MODEL_KIND = "TPU v5 lite"

#: Live ``[tile, lanes]`` 32-bit values in one stage-1 step (counts, the
#: gathered membership words, the select/compare temporaries).
_LIVE_BLOCKS = 4

_MIN_TILE = 8
_MAX_TILE = 1024


class KernelChoice(NamedTuple):
    """One autotuner decision: the vertex tile and the kernel layout."""

    tile: int
    stages: int          # 1 = sequential accumulate, 2 = split-phase


_CACHE: Dict[Tuple, KernelChoice] = {}


def _next_pow2(x: int) -> int:
    p = _MIN_TILE
    while p < x:
        p *= 2
    return p


def candidate_tiles(n: int) -> Tuple[int, ...]:
    """Power-of-two tiles from 8 up to the first one covering ``n``."""
    top = min(_next_pow2(n), _MAX_TILE)
    out, t = [], _MIN_TILE
    while t <= top:
        out.append(t)
        t *= 2
    return tuple(out)


def _blocks(n: int, tile: int) -> int:
    return -(-n // tile)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode is a platform decision: only on the CPU platform
    (the tests) or on request.  Everywhere else the kernel compiles or the
    call raises — no silent interpreter on a device."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _device_kind() -> str:
    return jax.devices()[0].device_kind


def _model(platform: str) -> Tuple[peaks.DevicePeaks, float]:
    """(peak-table entry, per-grid-step seconds) for ``platform``."""
    if platform == "tpu":
        entry = peaks.peaks_for(_device_kind())
        return entry, entry.grid_step_s
    return peaks.peaks_for(INTERPRET_MODEL_KIND), INTERPRET_STEP_S


def predict_cost(n: int, w: int, lanes: int, k: int, *, tile: int,
                 stages: int, platform: str,
                 stacked: Optional[bool] = None) -> Optional[float]:
    """Modeled seconds for one kernel invocation, or None if infeasible.

    ``stacked`` (default ``k > 1``) selects the stacked kernel's stages=1
    grid, which steps per lane rather than per lane block.  The roofline
    part (word-ops vs HBM bytes) comes from ``RooflineCounts.terms``; the
    grid term is ``steps × per-step overhead``.
    """
    from repro.kernels.bitset_ops import LANE_BLOCK

    entry, step_s = _model(platform)
    stacked = k > 1 if stacked is None else stacked
    blocks = _blocks(n, tile)
    padded = blocks * tile
    word_bytes = 4

    if stages == 2:
        # Stage 1 holds one table block, every lane's two masks and the
        # [tile, L] intermediates; partials go out [K·blocks, 4, L].
        step_lanes = lanes
        steps = k * blocks + (1 if k * blocks > 1 else 0)
        hbm = (k * padded * w + 2 * lanes * w
               + 2 * k * blocks * 4 * lanes + 4 * lanes) * word_bytes
    elif stacked:
        # Per-lane scalar-prefetch grid: the lane's instance table is
        # streamed once per lane.
        step_lanes = 1
        steps = lanes * blocks
        hbm = (lanes * padded * w + 2 * lanes * w + 4 * lanes) * word_bytes
    else:
        # Lane-block grid: the table is re-streamed per lane block.
        step_lanes = LANE_BLOCK if lanes % LANE_BLOCK == 0 else lanes
        steps = (lanes // step_lanes) * blocks
        hbm = ((lanes // step_lanes) * padded * w + 2 * lanes * w
               + 4 * lanes) * word_bytes
    working = (tile * w + 2 * w * step_lanes
               + _LIVE_BLOCKS * tile * step_lanes) * word_bytes
    if working > entry.vmem_budget_bytes:
        return None
    # ~4 word-ops per (lane, vertex, word): and, popcount, compare, add.
    ops = 4.0 * lanes * padded * w * (k if stages == 2 else 1)
    terms = RooflineCounts(flops=ops, hbm_bytes=float(hbm)).terms(
        entry.word_ops_per_s, entry.hbm_bytes_per_s, entry.ici_bytes_per_s)
    return max(terms["compute_s"], terms["memory_s"]) + steps * step_s


def choose(n: int, w: int, lanes: int = 1, k: int = 1,
           platform: Optional[str] = None,
           stacked: Optional[bool] = None) -> KernelChoice:
    """Pick (tile, stages) for a ``(n, w, L, K)`` kernel shape.

    Cached per shape and device; a prior :func:`measured_choice` sweep for
    the same key takes precedence over the analytic model.  On a TPU the
    model reads the chip's ``repro.peaks`` entry and an unknown
    ``device_kind`` raises.
    """
    platform = platform or jax.default_backend()
    stacked = k > 1 if stacked is None else stacked
    kind = _device_kind() if platform == "tpu" else None
    key = (n, w, lanes, k, stacked, platform, kind)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    best_cost, best = None, None
    for tile in candidate_tiles(n):
        for stages in (2, 1):
            cost = predict_cost(n, w, lanes, k, tile=tile, stages=stages,
                                platform=platform, stacked=stacked)
            if cost is None:
                continue
            if best_cost is None or cost < best_cost:
                best_cost, best = cost, KernelChoice(tile, stages)
    if best is None:                       # every candidate over budget
        best = KernelChoice(_MIN_TILE, 1)
    _CACHE[key] = best
    return best


def measured_choice(n: int, w: int, lanes: int = 1, k: int = 1, *,
                    repeat: int = 3,
                    platform: Optional[str] = None) -> KernelChoice:
    """Measured sweep: time the real kernel per candidate and cache the
    winner under the same key :func:`choose` consults.

    Synthetic uint32 operands; the sweep exercises ``count_stats`` for
    K = 1 and ``stacked_count_stats`` otherwise.  Intended for offline
    tuning (benchmarks) — per-candidate compile + run is far too slow for
    a hot path.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import bitset_ops

    platform = platform or jax.default_backend()
    stacked = k > 1
    kind = _device_kind() if platform == "tpu" else None
    rng = np.random.default_rng(0)

    def bits(shape):
        return jnp.asarray(
            rng.integers(0, 2**32, shape, dtype=np.uint64).astype(np.uint32))

    tables = bits((k, n, w)) if stacked else bits((n, w))
    mask, valid = bits((lanes, w)), bits((lanes, w))
    inst = jnp.asarray(rng.integers(0, k, lanes).astype(np.int32))

    best_t, best = None, None
    for tile in candidate_tiles(n):
        for stages in (2, 1):
            if predict_cost(n, w, lanes, k, tile=tile, stages=stages,
                            platform=platform) is None:
                continue
            if stacked:
                fn = jax.jit(lambda t_, i, m, v, _tl=tile, _st=stages:
                             bitset_ops.stacked_count_stats(
                                 t_, i, m, v, tile=_tl, stages=_st))
                args = (tables, inst, mask, valid)
            else:
                fn = jax.jit(lambda t_, m, v, _tl=tile, _st=stages:
                             bitset_ops.count_stats(t_, m, v, tile=_tl,
                                                    stages=_st))
                args = (tables, mask, valid)
            jax.block_until_ready(fn(*args))           # compile + warm
            t = min(_time_once(fn, args) for _ in range(repeat))
            if best_t is None or t < best_t:
                best_t, best = t, KernelChoice(tile, stages)
    if best is None:
        best = choose(n, w, lanes, k, platform)
    _CACHE[(n, w, lanes, k, stacked, platform, kind)] = best
    return best


def _time_once(fn, args) -> float:
    import time
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def clear_cache() -> None:
    """Drop every cached decision (tests / re-tuning)."""
    _CACHE.clear()
