"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The scale-out path beyond TP=16: layers are split into S stages mapped to
a ``stage`` mesh axis; activations advance stage-to-stage with
``collective_permute`` inside ``shard_map``.  The steady-state loop runs
S + M - 1 ticks for M microbatches (fill + drain), the standard GPipe
schedule; each device computes its stage's layer stack per tick.

This module is exercised at small scale (tests/test_train_substrate.py,
8 host devices) — the production dry-run mesh uses DP x TP, with PP as the
documented growth axis past a pod (DESIGN.md §6).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_forward(stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
                     params_stacked: PyTree, x_mb: jnp.ndarray,
                     mesh: Mesh, stage_axis: str = "stage") -> jnp.ndarray:
    """Run M microbatches through S pipeline stages.

    stage_fn(stage_params, x) -> x  applies ONE stage's layer stack.
    params_stacked: leaves with leading dim S (sharded over stage_axis).
    x_mb: [M, mb, ...] microbatched input (replicated across stages).
    Returns [M, mb, ...] outputs (as produced by the last stage).
    """
    s = mesh.shape[stage_axis]
    m = x_mb.shape[0]

    def body(params, xs):
        sid = jax.lax.axis_index(stage_axis)
        p_local = jax.tree_util.tree_map(lambda a: a[0], params)
        n_ticks = s + m - 1

        def tick(carry, t):
            buf, outs = carry          # buf: [mb, ...] current activation
            # stage 0 injects microbatch t (if any); others use permuted.
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = xs[mb_idx]
            cur = jnp.where((sid == 0) & (t < m), inject, buf)
            y = stage_fn(p_local, cur)
            # last stage emits microbatch (t - (s-1)) at ticks >= s-1.
            emit_idx = jnp.clip(t - (s - 1), 0, m - 1)
            do_emit = (sid == s - 1) & (t >= s - 1)
            outs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, emit_idx, 0),
                lambda o: o, outs)
            # hand off to the next stage (ring permute; last->0 unused).
            nxt = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % s) for i in range(s)])
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (buf, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                      jnp.arange(n_ticks))
        # outs only valid on the last stage; broadcast it to all
        # (ppermute is a strict permutation, so gather + select instead).
        outs = jax.lax.all_gather(outs, stage_axis)[s - 1]
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(stage_axis),
                                         params_stacked),
                  P()),
        out_specs=P(), check_vma=False)
    return fn(params_stacked, x_mb)
