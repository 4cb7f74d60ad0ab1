"""Universal Pallas bitset-kernel library (DESIGN.md §5).

Every problem family in this repo funnels its per-search-node work through
one shape: a ``uint32[n, w]`` table of packed bitset rows (adjacency for
vertex cover, closed neighborhoods for dominating set, one table per slot
for the stacked service), ANDed against a per-lane ``uint32[w]`` mask,
popcounted per row, and reduced to a handful of scalars (max count,
argmax with smallest-id tie-break, count sum, mask popcount).  This module
is that machinery ONCE, as a small kernel library every problem binds to
instead of forking its own kernel:

  ``count_stats``         — THE masked-popcount pass over one table
                            (DESIGN.md §5.2: the contract);
  ``stacked_count_stats`` — the batched ``uint32[K, n, w]`` variant for the
                            multi-tenant service (DESIGN.md §5.3);
  ``popcount_reduce``     — per-row popcount sum (set cardinalities);
  ``masked_row_reduce``   — OR/AND-accumulate of table rows selected by a
                            bitset (e.g. neighborhoods of a chosen set).

Layout (DESIGN.md §5.1): a table block is ``(tile, w)`` — vertices on the
sublane axis (``tile`` a multiple of 8), the whole word row on the lane
axis — and the per-lane masks enter transposed, ``uint32[w, L]``, so the
LANES sit on the minor axis.  A block's counts are one ``int32[tile, L]``
value built word by word (no gather), the per-vertex reductions run down
the sublanes, and each statistic leaves as a row of an ``int32[4, L]``
output.  Every block meets the TPU rule that its last two dimensions are
divisible by (8, 128) or equal to the array's: mask blocks are the whole
``(w, L)`` or ``(w, 128)`` lane slabs.

Two kernel layouts implement the contract (selected by ``stages``, chosen
per shape by ``repro.kernels.autotune`` when left ``None``):

  stages=2 — SPLIT-PHASE (DESIGN.md §5.5): stage 1 is a grid over vertex
             tile-blocks only, every lane batched inside the block body,
             writing per-block partial stats to a ``[blocks, 4, L]``
             scratch; stage 2 is one small combine kernel whose in-order
             strict-greater merge keeps the smallest-id tie-break (block
             ids ascend, so the first block reaching the global best owns
             the answer).  No sequential grid axis in stage 1.
  stages=1 — the grid ``(lane blocks, tiles)`` with the tile axis
             innermost/sequential, accumulating into the ``(4, lanes)``
             output block.  The stacked variant is the per-lane
             scalar-prefetch grid (one instance block per lane-step).

``interpret=None`` interprets only on the CPU platform (the tests); on any
other platform the kernel compiles or the call raises — there is no
silent interpreter fallback on a device.  Validated against the jnp
oracles in ``ref.py`` and the numpy oracles in ``tests/test_bitset_ops.py``
/ ``tests/test_split_phase.py``; ``vmap`` over lane operands (as the engine
applies per-lane ``evaluate``) lifts the lane axis into the kernel grid
for either layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

#: Column layout of the ``count_stats`` / ``stacked_count_stats`` output —
#: the whole per-node reduction that leaves VMEM (DESIGN.md §5.2).
BEST, ARG, SUM, MASK_COUNT = 0, 1, 2, 3

#: Lanes per grid step of the lane-blocked (stages=1) kernels when the
#: lane count is a multiple of it; otherwise one block spans every lane.
LANE_BLOCK = 128

#: Sublane tiling of 32-bit values: a table tile must be a multiple.
SUBLANES = 8

_NO_ARG = 2**30


def _validate_tile(tile: int, stages: int) -> None:
    """The tile contract: a positive multiple of the sublane tiling
    everywhere; a power of two where the split-phase combine requires it
    (block args must ascend uniformly for the smallest-id tie-break)."""
    if stages not in (1, 2):
        raise ValueError(f"stages must be 1 or 2, got {stages!r}")
    if not isinstance(tile, int) or isinstance(tile, bool) or tile < 1:
        raise ValueError(f"tile must be a positive int, got {tile!r}")
    if tile % SUBLANES:
        raise ValueError(
            f"tile must be a multiple of {SUBLANES} (the sublane tiling), "
            f"got {tile}")
    if stages == 2 and tile & (tile - 1):
        raise ValueError(
            f"tile must be a power of two for the split-phase (stages=2) "
            f"kernels, got {tile}")


def _resolve_shape(n: int, w: int, lanes: int, k: int,
                   tile: Optional[int], stages: Optional[int], *,
                   stacked: bool):
    """Fill unset (tile, stages) from the per-shape autotuner cache."""
    if tile is None or stages is None:
        choice = autotune.choose(n, w, lanes=lanes, k=k, stacked=stacked)
        tile = choice.tile if tile is None else tile
        stages = choice.stages if stages is None else stages
    return tile, stages


def _lane_block(lanes: int) -> int:
    return LANE_BLOCK if lanes % LANE_BLOCK == 0 else lanes


def _pad_rows(table: jnp.ndarray, tile: int) -> jnp.ndarray:
    pad = (-table.shape[-2]) % tile
    if pad:
        width = [(0, 0)] * (table.ndim - 2) + [(0, pad), (0, 0)]
        table = jnp.pad(table, width)
    return table


# ---------------------------------------------------------------------------
# block math shared by every layout (lanes on the minor axis)
# ---------------------------------------------------------------------------

def _vertex_ids(base, tile: int) -> jnp.ndarray:
    """int32[tile, 1]: the vertex id of each sublane row of a block."""
    return base + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)


def _member_bits(bits_t: jnp.ndarray, vid: jnp.ndarray, n: int):
    """bool[tile, L]: is bit ``vid`` set in each lane's ``uint32[w]`` set
    (``bits_t`` transposed, ``[w, L]``), for a real vertex (``vid < n``)?
    The word is picked by a select per word row — no in-kernel gather."""
    word = jax.lax.shift_right_logical(vid, 5)                 # vid // 32
    shift = jnp.bitwise_and(vid, 31).astype(jnp.uint32)        # vid % 32
    rows = jnp.zeros((vid.shape[0], bits_t.shape[1]), jnp.uint32)
    for j in range(bits_t.shape[0]):
        rows = jnp.where(word == j, bits_t[j:j + 1, :], rows)
    bit = jnp.bitwise_and(jax.lax.shift_right_logical(rows, shift),
                          jnp.uint32(1))
    return (bit == jnp.uint32(1)) & (vid < n)


def _masked_counts(table: jnp.ndarray, mask_t: jnp.ndarray) -> jnp.ndarray:
    """int32[tile, L] = popcount(table[v] & mask[l]), one word at a time."""
    cnts = None
    for j in range(table.shape[1]):
        word = jnp.bitwise_and(table[:, j:j + 1], mask_t[j:j + 1, :])
        c = jax.lax.population_count(word).astype(jnp.int32)
        cnts = c if cnts is None else cnts + c
    return cnts


def _mask_count(mask_t: jnp.ndarray) -> jnp.ndarray:
    """int32[1, L] = popcount of each lane's whole mask."""
    return jnp.sum(jax.lax.population_count(mask_t).astype(jnp.int32),
                   axis=0, keepdims=True)


def _block_stats(table, mask_t, valid_t, base, *, tile: int, n: int):
    """(best, arg, sum), each int32[1, L], of one ``[tile, w]`` block
    against every lane: block-local max count over valid vertices (-1 if
    none), its smallest vertex id (-1 if none) and the block's Σ counts."""
    vid = _vertex_ids(base, tile)
    cnts = jnp.where(_member_bits(valid_t, vid, n),
                     _masked_counts(table, mask_t), jnp.int32(-1))
    best = jnp.max(cnts, axis=0, keepdims=True)
    arg = jnp.min(jnp.where(cnts == best, vid, jnp.int32(_NO_ARG)),
                  axis=0, keepdims=True)
    arg = jnp.where(best < 0, jnp.int32(-1), arg)
    ssum = jnp.sum(jnp.maximum(cnts, 0), axis=0, keepdims=True)
    return best, arg, ssum


def _stat_rows(best, arg, ssum, mc) -> jnp.ndarray:
    """Stack four int32[1, L] rows into the int32[4, L] output block."""
    row = jax.lax.broadcasted_iota(jnp.int32, (4, best.shape[1]), 0)
    return jnp.where(row == BEST, best,
                     jnp.where(row == ARG, arg,
                               jnp.where(row == SUM, ssum, mc)))


def _merge(acc, blk):
    """Fold block stats into running stats; strict '>' keeps the earlier
    (smaller-id) block's argmax on ties."""
    best, arg, ssum = acc
    b_best, b_arg, b_sum = blk
    better = b_best > best
    return (jnp.where(better, b_best, best), jnp.where(better, b_arg, arg),
            ssum + b_sum)


def _neutral(lanes: int):
    neg = jnp.full((1, lanes), -1, jnp.int32)
    return neg, neg, jnp.zeros((1, lanes), jnp.int32)


# ---------------------------------------------------------------------------
# count_stats: the masked-popcount contract (DESIGN.md §5.2)
# ---------------------------------------------------------------------------

def _accumulate_body(table_ref, mask_ref, valid_ref, out_ref, *,
                     tile: int, n: int):
    """stages=1 body: fold this tile into the ``(4, lanes)`` output block,
    initialised on the first tile of the sequential axis."""
    t = pl.program_id(1)
    mask_t = mask_ref[...]
    lanes = mask_t.shape[1]

    @pl.when(t == 0)
    def _init():
        out_ref[...] = _stat_rows(*_neutral(lanes), _mask_count(mask_t))

    cur = out_ref[...]
    best, arg, ssum = _merge(
        (cur[BEST:BEST + 1], cur[ARG:ARG + 1], cur[SUM:SUM + 1]),
        _block_stats(table_ref[...], mask_t, valid_ref[...], t * tile,
                     tile=tile, n=n))
    out_ref[...] = _stat_rows(best, arg, ssum,
                              cur[MASK_COUNT:MASK_COUNT + 1])


def _count_stats_seq(table, mask_t, valid_t, *, tile: int, n: int,
                     interpret: bool) -> jnp.ndarray:
    """stages=1: grid (lane blocks, tiles), tiles sequential."""
    w, lanes = mask_t.shape
    lb = _lane_block(lanes)
    table = _pad_rows(table, tile)
    tiles = table.shape[0] // tile
    return pl.pallas_call(
        functools.partial(_accumulate_body, tile=tile, n=n),
        grid=(lanes // lb, tiles),
        in_specs=[
            pl.BlockSpec((tile, w), lambda l, t: (t, 0)),
            pl.BlockSpec((w, lb), lambda l, t: (0, l)),
            pl.BlockSpec((w, lb), lambda l, t: (0, l)),
        ],
        out_specs=pl.BlockSpec((4, lb), lambda l, t: (0, l)),
        out_shape=jax.ShapeDtypeStruct((4, lanes), jnp.int32),
        interpret=interpret,
    )(table, mask_t, valid_t)


def _combine_body(part_ref, out_ref):
    """Split-phase stage 2 (DESIGN.md §5.5): merge ``[B, 4, L]`` partials
    in block order.  Blocks cover ascending id ranges, so the in-order
    strict-greater merge keeps the smallest id among global ties; the mask
    popcount is block-invariant for a bound lane and 0 otherwise (max)."""
    lanes = part_ref.shape[2]

    def step(b, carry):
        best, arg, ssum, mc = carry
        p = part_ref[b]                                    # [4, L]
        best, arg, ssum = _merge(
            (best, arg, ssum),
            (p[BEST:BEST + 1], p[ARG:ARG + 1], p[SUM:SUM + 1]))
        return best, arg, ssum, jnp.maximum(mc, p[MASK_COUNT:MASK_COUNT + 1])

    best, arg, ssum, mc = jax.lax.fori_loop(
        0, part_ref.shape[0], step,
        _neutral(lanes) + (jnp.zeros((1, lanes), jnp.int32),))
    out_ref[...] = _stat_rows(best, arg, ssum, mc)


def _combine(part: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    b, _, lanes = part.shape
    return pl.pallas_call(
        _combine_body,
        grid=(1,),
        in_specs=[pl.BlockSpec((b, 4, lanes), lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((4, lanes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((4, lanes), jnp.int32),
        interpret=interpret,
    )(part)


def _count_stats_split(table, mask_t, valid_t, *, tile: int, n: int,
                       interpret: bool) -> jnp.ndarray:
    """stages=2: grid over tile-blocks only, lanes batched in-block, then
    one combine launch (elided when a single block covers the table)."""
    w, lanes = mask_t.shape
    table = _pad_rows(table, tile)
    blocks = table.shape[0] // tile

    def stage1(table_ref, mask_ref, valid_ref, out_ref):
        b = pl.program_id(0)
        mask = mask_ref[...]
        out_ref[0] = _stat_rows(
            *_block_stats(table_ref[...], mask, valid_ref[...], b * tile,
                          tile=tile, n=n),
            _mask_count(mask))

    part = pl.pallas_call(
        stage1,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((tile, w), lambda b: (b, 0)),
            pl.BlockSpec((w, lanes), lambda b: (0, 0)),
            pl.BlockSpec((w, lanes), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 4, lanes), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks, 4, lanes), jnp.int32),
        interpret=interpret,
    )(table, mask_t, valid_t)
    if blocks == 1:
        return part[0]
    return _combine(part, interpret=interpret)


def count_stats(table: jnp.ndarray, mask: jnp.ndarray, valid: jnp.ndarray,
                *, tile: Optional[int] = None, stages: Optional[int] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """The masked-popcount pass (DESIGN.md §5.2).

    ``table``: uint32[n, w] packed bitset rows; ``mask``/``valid``:
    uint32[L, w] per-lane masks.  Returns int32[L, 4] =
    ``(best_count, best_vertex, count_sum, mask_count)`` where
    ``count[v] = popcount(table[v] & mask)`` for vertices whose bit is set
    in ``valid`` (all others count -1), ``best_vertex`` breaks ties toward
    the smallest id (-1 when nothing is valid), ``count_sum`` is
    ``Σ max(count, 0)`` and ``mask_count = popcount(mask)``.

    ``tile``/``stages`` default to the autotuner's per-shape choice
    (DESIGN.md §5.6); ``interpret=None`` interprets on the CPU platform
    only and compiles everywhere else.
    """
    n, w = table.shape
    lanes = mask.shape[0]
    tile, stages = _resolve_shape(n, w, lanes, 1, tile, stages,
                                  stacked=False)
    _validate_tile(tile, stages)
    run = _count_stats_seq if stages == 1 else _count_stats_split
    out = run(table, mask.T, valid.T, tile=tile, n=n,
              interpret=autotune.resolve_interpret(interpret))
    return out.T


# ---------------------------------------------------------------------------
# stacked_count_stats: the batched uint32[K, n, w] variant (DESIGN.md §5.3)
# ---------------------------------------------------------------------------

def _stacked_seq(tables, inst, mask, valid, *, tile: int, n: int,
                 interpret: bool) -> jnp.ndarray:
    """stages=1: one lane per outer grid step, table block selected by
    scalar prefetch.  A lane's mask is a ``(w, 1)`` column (a one-lane
    slab of the transposed layout).  Idle (inst < 0) lanes are parked
    before the call: their masks are zeroed (so the output is the
    (-1, -1, 0, 0) no-valid row) and their prefetch id is clipped only to
    keep the DMA in range."""
    k, _, w = tables.shape
    lanes = mask.shape[0]
    idle = inst.astype(jnp.int32) < 0
    mask = jnp.where(idle[:, None], jnp.uint32(0), mask)[:, :, None]
    valid = jnp.where(idle[:, None], jnp.uint32(0), valid)[:, :, None]
    inst = jnp.clip(inst.astype(jnp.int32), 0, k - 1)
    tables = _pad_rows(tables, tile)
    tiles = tables.shape[1] // tile
    sq = pl.squeezed

    def kernel(inst_ref, table_ref, mask_ref, valid_ref, out_ref):
        del inst_ref                              # consumed by the index map
        _accumulate_body(table_ref, mask_ref, valid_ref, out_ref,
                         tile=tile, n=n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(lanes, tiles),
        in_specs=[
            pl.BlockSpec((sq, tile, w),
                         lambda l, t, inst_ref: (inst_ref[l], t, 0)),
            pl.BlockSpec((sq, w, 1), lambda l, t, inst_ref: (l, 0, 0)),
            pl.BlockSpec((sq, w, 1), lambda l, t, inst_ref: (l, 0, 0)),
        ],
        out_specs=pl.BlockSpec((sq, 4, 1),
                               lambda l, t, inst_ref: (l, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((lanes, 4, 1), jnp.int32),
        interpret=interpret,
    )(inst, tables, mask, valid)
    return out[:, :, 0]


def _stacked_split(tables, inst, mask, valid, *, tile: int, n: int,
                   interpret: bool) -> jnp.ndarray:
    """stages=2: grid (K, blocks) — each step loads ONE instance's tile
    block and reduces it against every lane bound to that instance (other
    lanes' masks are zeroed in-body, so their partials stay the neutral
    (-1, -1, 0, 0) row).  Table traffic is K × blocks DMAs regardless of
    the lane count or how many lanes are idle: an unbound (inst < 0) lane
    matches no instance step, causes no table traffic of its own, and
    combines to the parked (-1, -1, 0, 0) output."""
    k, _, w = tables.shape
    lanes = mask.shape[0]
    tables = _pad_rows(tables, tile)
    blocks = tables.shape[1] // tile
    inst2 = inst.astype(jnp.int32).reshape(1, lanes)

    def stage1(tables_ref, inst_ref, mask_ref, valid_ref, out_ref):
        ki = pl.program_id(0)
        b = pl.program_id(1)
        bound = inst_ref[...] == ki                  # [1, L]
        m = jnp.where(bound, mask_ref[...], jnp.uint32(0))
        v = jnp.where(bound, valid_ref[...], jnp.uint32(0))
        out_ref[0, 0] = _stat_rows(
            *_block_stats(tables_ref[0], m, v, b * tile, tile=tile, n=n),
            _mask_count(m))

    part = pl.pallas_call(
        stage1,
        grid=(k, blocks),
        in_specs=[
            pl.BlockSpec((1, tile, w), lambda ki, b: (ki, b, 0)),
            pl.BlockSpec((1, lanes), lambda ki, b: (0, 0)),
            pl.BlockSpec((w, lanes), lambda ki, b: (0, 0)),
            pl.BlockSpec((w, lanes), lambda ki, b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 4, lanes),
                               lambda ki, b: (ki, b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, blocks, 4, lanes), jnp.int32),
        interpret=interpret,
    )(tables, inst2, mask.T, valid.T)
    part = part.reshape(k * blocks, 4, lanes)
    out = part[0] if k * blocks == 1 else _combine(part, interpret=interpret)
    return out.T


def stacked_count_stats(tables: jnp.ndarray, inst: jnp.ndarray,
                        mask: jnp.ndarray, valid: jnp.ndarray, *,
                        tile: Optional[int] = None,
                        stages: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """``count_stats`` over stacked tables: uint32[K, n, w] + int32[L]
    instance ids -> int32[L, 4], lane ``l`` reduced against
    ``tables[inst[l]]``.

    Idle lanes (``inst < 0``, the service's ``NO_INSTANCE``) are PARKED:
    they bind to no table, generate no table traffic of their own, and
    return the no-valid row ``(-1, -1, 0, 0)`` — the engine ignores their
    outputs, and this contract makes that safe by construction.

    Layouts (DESIGN.md §5.3/§5.5): stages=2 runs a grid over
    ``(instance, tile-block)`` with every lane batched in-body — table
    traffic is K·blocks DMAs, independent of the lane count; stages=1 is
    the per-lane scalar-prefetch grid — L·blocks DMAs, one instance block
    per lane-step.  Defaults come from the autotuner.
    """
    k, n, w = tables.shape
    lanes = mask.shape[0]
    tile, stages = _resolve_shape(n, w, lanes, k, tile, stages,
                                  stacked=True)
    _validate_tile(tile, stages)
    run = _stacked_seq if stages == 1 else _stacked_split
    return run(tables, inst, mask, valid, tile=tile, n=n,
               interpret=autotune.resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# popcount_reduce: per-lane set cardinalities
# ---------------------------------------------------------------------------

def _popcount_kernel(rows_ref, out_ref):
    out_ref[...] = _mask_count(rows_ref[...])


def popcount_reduce(rows: jnp.ndarray, *,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """uint32[L, w] -> int32[L]: popcount of each packed row (set sizes)."""
    lanes, w = rows.shape
    lb = _lane_block(lanes)
    out = pl.pallas_call(
        _popcount_kernel,
        grid=(lanes // lb,),
        in_specs=[pl.BlockSpec((w, lb), lambda l: (0, l))],
        out_specs=pl.BlockSpec((1, lb), lambda l: (0, l)),
        out_shape=jax.ShapeDtypeStruct((1, lanes), jnp.int32),
        interpret=autotune.resolve_interpret(interpret),
    )(rows.T)
    return out[0]


# ---------------------------------------------------------------------------
# masked_row_reduce: OR/AND-accumulate of selected table rows
# ---------------------------------------------------------------------------

def _fold_rows(x: jnp.ndarray, bitop) -> jnp.ndarray:
    """[tile, L] -> [1, L] by a static halving tree (tile a power of two)."""
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = bitop(x[:half], x[half:])
    return x


def _row_reduce_kernel(table_ref, sel_ref, out_ref, *, tile: int, n: int,
                       op: str):
    t = pl.program_id(1)
    ident = jnp.uint32(0) if op == "or" else jnp.uint32(0xFFFFFFFF)
    bitop = jnp.bitwise_or if op == "or" else jnp.bitwise_and
    w, lanes = out_ref.shape

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.full((w, lanes), ident, jnp.uint32)

    table = table_ref[...]
    selected = _member_bits(sel_ref[...], _vertex_ids(t * tile, tile), n)
    row = jax.lax.broadcasted_iota(jnp.int32, (w, lanes), 0)
    acc = out_ref[...]
    for j in range(w):
        word = _fold_rows(jnp.where(selected, table[:, j:j + 1], ident),
                          bitop)                               # [1, L]
        acc = jnp.where(row == j, bitop(acc, word), acc)
    out_ref[...] = acc


def masked_row_reduce(table: jnp.ndarray, select: jnp.ndarray, *,
                      op: str = "or", tile: int = 128,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Bitwise OR (or AND) of the rows of ``table`` (uint32[n, w]) whose
    bit is set in ``select`` (uint32[L, w]) -> uint32[L, w].  The OR form
    with an adjacency table is ``N(S)`` for the selected set S; the AND
    form intersects constraint rows.  Empty selection yields the identity
    (all-zeros / all-ones)."""
    if op not in ("or", "and"):
        raise ValueError(f"unknown reduce op {op!r}")
    n, w = table.shape
    if not isinstance(tile, int) or isinstance(tile, bool) or tile < 1:
        raise ValueError(f"tile must be a positive int, got {tile!r}")
    if tile & (tile - 1) or tile % SUBLANES:
        raise ValueError(
            f"tile must be a power of two and a multiple of {SUBLANES}, "
            f"got {tile}")
    lanes = select.shape[0]
    lb = _lane_block(lanes)
    table = _pad_rows(table, tile)
    tiles = table.shape[0] // tile
    out = pl.pallas_call(
        functools.partial(_row_reduce_kernel, tile=tile, n=n, op=op),
        grid=(lanes // lb, tiles),
        in_specs=[
            pl.BlockSpec((tile, w), lambda l, t: (t, 0)),
            pl.BlockSpec((w, lb), lambda l, t: (0, l)),
        ],
        out_specs=pl.BlockSpec((w, lb), lambda l, t: (0, l)),
        out_shape=jax.ShapeDtypeStruct((w, lanes), jnp.uint32),
        interpret=autotune.resolve_interpret(interpret),
    )(table, select.T)
    return out.T


# ---------------------------------------------------------------------------
# problem-facing bindings (DESIGN.md §5.4)
# ---------------------------------------------------------------------------

def domination_stats(cadj: jnp.ndarray, dominated: jnp.ndarray,
                     cand: jnp.ndarray, fullm: jnp.ndarray, *,
                     tile: Optional[int] = None,
                     stages: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Dominating set's node statistics as a ``count_stats`` binding:
    mask = the undominated set, valid = the candidate set.  ``cadj``:
    uint32[n, w] CLOSED adjacency; ``dominated``/``cand``: uint32[L, w];
    ``fullm``: uint32[w] real-vertex mask.  Returns int32[L, 3] =
    ``(best_coverage, branch_vertex, undominated)`` — coverage is
    ``|N[v] \\ dominated|`` per candidate, the tie-break is smallest-id and
    ``undominated`` comes free as the pass's mask popcount."""
    mask = jnp.bitwise_and(fullm[None, :], jnp.bitwise_not(dominated))
    out = count_stats(cadj, mask, cand, tile=tile, stages=stages,
                      interpret=interpret)
    return jnp.stack([out[:, BEST], out[:, ARG], out[:, MASK_COUNT]], axis=1)
