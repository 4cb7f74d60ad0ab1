"""Kernel microbenchmarks (CPU wall time of the jnp reference path +
interpret-mode correctness deltas for the Pallas bodies).

Absolute CPU µs are not TPU predictions; the table documents (a) the
shapes each kernel is exercised at, (b) ref-vs-kernel max abs error, and
(c) the ref path's CPU throughput as a regression canary.

``run_node_eval`` additionally measures the solver's actual unit of work
— fused ``Problem.evaluate`` nodes/sec, batched over lanes — for BOTH
kernel-layer problem families (DESIGN.md §5.4): vertex cover (legacy
three-callback adapter vs fused jnp vs fused+Pallas) and dominating set
(fused jnp vs fused+Pallas), and records the trajectory in
``BENCH_node_eval.json`` at the repo root (DESIGN.md §3/§5).  Each
variant row carries its execution metadata — ``mode`` ("jnp" vs the
Pallas path's "interpret"/"compiled") and, for Pallas variants, the
autotuned ``tile``/``stages`` (DESIGN.md §5.6) — so a recorded number is
attributable to the configuration that produced it.  On CPU the Pallas
variants run the kernel bodies in interpret mode, so their absolute
numbers are correctness canaries, not speed claims.

``--quick`` measures a smaller shape and records it under the ``"quick"``
subtree of the JSON (the full-size trajectory stays at top level);
``--gate`` compares the fresh numbers against the committed baseline of
the SAME subtree and exits non-zero on a >20% nodes/sec regression for
any (family, variant) pair — the CI bench-smoke regression gate.  A
failed gate does not overwrite the baseline.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_meta, timed, write_csv
from repro import registry
from repro.core.api import INF_VALUE
from repro.kernels import bitset_ops, ref
from repro.kernels.bitset_degree import degree_argmax
from repro.problems.dominating_set import DSState
from repro.problems.graphs import gnp_graph, full_mask, num_words
from repro.problems.vertex_cover import VCState, make_vertex_cover_callbacks

#: Gate threshold: fail on a >20% nodes/sec drop vs the committed baseline.
GATE_REGRESSION = 0.20

BENCH_JSON = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_node_eval.json"))


def run(quick: bool = False) -> list:
    rows = []

    # bitset degree/argmax
    for (n, pr, lanes) in [(300, 0.05, 16)] + ([] if quick else
                                               [(512, 0.1, 64)]):
        g = gnp_graph(n, pr, seed=n)
        adj = jnp.asarray(g.adj)
        alive = jnp.tile(jnp.asarray(full_mask(n))[None, :], (lanes, 1))
        fn = jax.jit(lambda a, m: ref.degree_argmax_ref(a, m))
        t, out_ref = timed(lambda: np.asarray(fn(adj, alive)))
        out_pl = degree_argmax(adj, alive, interpret=True)
        err = int(jnp.max(jnp.abs(out_pl - out_ref)))
        rows.append({"kernel": "bitset_degree",
                     "shape": f"n{n}_L{lanes}",
                     "ref_ms": round(t * 1e3, 2),
                     "max_abs_err": str(err)})

    # stacked bitset stats (the service's batched kernel, DESIGN.md §5.3)
    for (k, n, lanes) in [(4, 128, 16)] + ([] if quick else [(8, 256, 64)]):
        key2 = jax.random.PRNGKey(k)
        w = (n + 31) // 32
        kt, km, kv, ki = jax.random.split(key2, 4)

        def bits(kk, shape):
            return jax.random.randint(kk, shape, 0, jnp.iinfo(jnp.int32).max,
                                      jnp.int32).astype(jnp.uint32)

        tables = bits(kt, (k, n, w))
        mask = bits(km, (lanes, w))
        valid = bits(kv, (lanes, w))
        inst = jax.random.randint(ki, (lanes,), 0, k, jnp.int32)
        fn = jax.jit(lambda t_, i, m, v: ref.stacked_count_stats_ref(
            t_, i, m, v))
        t, out_ref = timed(lambda: np.asarray(fn(tables, inst, mask, valid)))
        out_pl = bitset_ops.stacked_count_stats(tables, inst, mask, valid,
                                                interpret=True)
        err = int(jnp.max(jnp.abs(out_pl - out_ref)))
        rows.append({"kernel": "bitset_stacked",
                     "shape": f"k{k}_n{n}_L{lanes}",
                     "ref_ms": round(t * 1e3, 2),
                     "max_abs_err": str(err)})
    return rows


def _lane_states(graph, lanes: int) -> VCState:
    """Batch of distinct mid-search states (varied alive masks) so the
    evaluate benchmark sees realistic, non-constant-foldable inputs."""
    key = jax.random.PRNGKey(0)
    w = graph.words
    keep = jax.random.bernoulli(key, 0.8, (lanes, graph.n))
    masks = np.zeros((lanes, w), np.uint32)
    kp = np.asarray(keep)
    for l in range(lanes):
        for v in range(graph.n):
            if kp[l, v]:
                masks[l, v // 32] |= np.uint32(1) << np.uint32(v % 32)
    full = np.asarray(full_mask(graph.n))
    return VCState(alive=jnp.asarray(masks),
                   cover=jnp.asarray((~masks) & full[None, :]),
                   size=jnp.asarray(np.bitwise_count(
                       (~masks) & full[None, :]).sum(axis=1).astype(np.int32)))


def _ds_lane_states(graph, lanes: int) -> DSState:
    """Batch of distinct mid-search dominating-set states (varied dominated
    and candidate masks) mirroring ``_lane_states``."""
    key = jax.random.PRNGKey(1)
    w = graph.words
    kd, kc = jax.random.split(key)
    dom = np.asarray(jax.random.bernoulli(kd, 0.3, (lanes, graph.n)))
    cnd = np.asarray(jax.random.bernoulli(kc, 0.7, (lanes, graph.n)))
    dominated = np.zeros((lanes, w), np.uint32)
    cand = np.zeros((lanes, w), np.uint32)
    for l in range(lanes):
        for v in range(graph.n):
            if dom[l, v]:
                dominated[l, v // 32] |= np.uint32(1) << np.uint32(v % 32)
            if cnd[l, v]:
                cand[l, v // 32] |= np.uint32(1) << np.uint32(v % 32)
    full = np.asarray(full_mask(graph.n))
    chosen = (~cand) & full[None, :]
    return DSState(dominated=jnp.asarray(dominated), cand=jnp.asarray(cand),
                   chosen=jnp.asarray(chosen),
                   size=jnp.asarray(np.bitwise_count(chosen).sum(
                       axis=1).astype(np.int32)))


def _time_variants(variants, states, lanes, n):
    """Time each (name, BinaryProblem) at the engine's unit of work.

    Variants carrying ``evaluate_batch`` (the Pallas problems) are timed
    through it — one kernel launch for all lanes, exactly what the fused
    round executes (DESIGN.md §5.5) — and annotated with the autotuned
    ``tile``/``stages`` their launch resolves to plus the execution
    ``mode``; plain variants go through ``vmap(evaluate)``.
    """
    from repro.kernels import autotune
    pallas_mode = ("compiled" if jax.default_backend() == "tpu"
                   else "interpret")
    choice = autotune.choose(n, num_words(n), lanes=lanes)
    best = jnp.full((lanes,), INF_VALUE, jnp.int32)
    out = {}
    for name, prob in variants:
        batched = prob.evaluate_batch is not None
        if batched:
            fn = jax.jit(lambda s, eb=prob.evaluate_batch: eb(s, best))
        else:
            fn = jax.jit(jax.vmap(
                lambda s, ev=prob.evaluate: ev(s, INF_VALUE)))
        # One batch is ~100µs — best-of-many keeps the regression gate
        # from tripping on OS scheduling noise.
        t, _ = timed(lambda: jax.block_until_ready(fn(states)), repeat=50)
        entry = {
            "sec_per_batch": round(t, 6),
            "nodes_per_sec": round(lanes / t, 1),
            "mode": pallas_mode if batched else "jnp",
        }
        if batched:
            entry["tile"], entry["stages"] = choice.tile, choice.stages
        out[name] = entry
    return out


def run_node_eval(quick: bool = False) -> dict:
    """Fused ``evaluate`` throughput per kernel-layer problem family:
    vc (legacy adapter / fused jnp / fused+Pallas) and ds (fused jnp /
    fused+Pallas) — the DESIGN.md §5.4 bindings measured at the solver's
    actual unit of work."""
    n, p, lanes = (60, 0.15, 16) if quick else (128, 0.1, 64)
    g = gnp_graph(n, p, seed=7)
    out = {"lanes": lanes,
           "unit": "node evaluations / second (CPU; pallas = interpret)"}
    # Problems are built through the registry's capability-checked front
    # door (ISSUE 4); only the pre-fusion baseline bypasses it, since the
    # legacy adapter is deliberately not a registered family.
    vc, ds = registry.get("vc"), registry.get("ds")
    out["vc"] = {
        "instance": f"gnp:{n}:{int(p * 100)}:7",
        "variants": _time_variants([
            ("legacy_callbacks", make_vertex_cover_callbacks(g)),
            ("fused_jnp", vc.build(g)),
            ("fused_pallas", vc.build(g, backend="pallas")),
        ], _lane_states(g, lanes), lanes, n)}
    out["ds"] = {
        "instance": f"gnp:{n}:{int(p * 100)}:7",
        "variants": _time_variants([
            ("fused_jnp", ds.build(g)),
            ("fused_pallas", ds.build(g, backend="pallas")),
        ], _ds_lane_states(g, lanes), lanes, n)}
    return out


def _gate_failures(baseline: dict, fresh: dict,
                   threshold: float = GATE_REGRESSION) -> list:
    """(family, variant) pairs whose fresh nodes/sec regressed more than
    ``threshold`` vs the committed baseline.  Pairs absent from the
    baseline (new variants, first run) pass vacuously."""
    fails = []
    for fam in ("vc", "ds"):
        base_vars = (baseline.get(fam) or {}).get("variants") or {}
        new_vars = (fresh.get(fam) or {}).get("variants") or {}
        for name, new in new_vars.items():
            old = base_vars.get(name) or {}
            old_nps = float(old.get("nodes_per_sec") or 0.0)
            new_nps = float(new["nodes_per_sec"])
            if old_nps > 0 and new_nps < (1.0 - threshold) * old_nps:
                fails.append(
                    f"{fam}/{name}: {new_nps:.0f} nodes/s is "
                    f"{100 * (1 - new_nps / old_nps):.1f}% below the "
                    f"baseline {old_nps:.0f}")
    return fails


def main(quick: bool = False, gate: bool = False) -> None:
    rows = run(quick)
    path = write_csv("kernel_micro.csv", rows,
                     ["kernel", "shape", "ref_ms", "max_abs_err"])
    for r in rows:
        print("kernels,%s,%s,%s,%s" % (r["kernel"], r["shape"],
                                       r["ref_ms"], r["max_abs_err"]))
    print(f"kernel_micro -> {path}")

    node_eval = run_node_eval(quick)
    # Merge-write: keep any per-family entries a previous run recorded that
    # this invocation did not re-measure (mirrors BENCH_service.json).
    # Quick runs live under their own "quick" subtree so the full-size
    # trajectory and the CI smoke shape never overwrite each other.
    merged = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                merged = json.load(f)
        except ValueError:
            merged = {}

    if gate:
        baseline = (merged.get("quick") or {}) if quick else merged
        fails = _gate_failures(baseline, node_eval)
        if fails:
            for msg in fails:
                print(f"GATE FAIL node_eval,{msg}")
            print(f"bench gate: {len(fails)} regression(s) > "
                  f"{int(GATE_REGRESSION * 100)}% — baseline NOT updated")
            sys.exit(1)

    node_eval["meta"] = bench_meta()
    if quick:
        sub = dict(merged.get("quick") or {})
        sub.update(node_eval)
        merged["quick"] = sub
    else:
        merged.update(node_eval)
    with open(BENCH_JSON, "w") as f:
        json.dump(merged, f, indent=2)
        f.write("\n")
    for fam in ("vc", "ds"):
        for name, v in node_eval[fam]["variants"].items():
            print("node_eval,%s,%s,%s,%s,%s" % (
                fam, name, v["sec_per_batch"], v["nodes_per_sec"],
                v["mode"]))
    print(f"node_eval -> {BENCH_JSON}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="small shapes; results under the 'quick' subtree")
    ap.add_argument("--gate", action="store_true",
                    help="fail (exit 1) on a >20%% nodes/sec regression "
                         "vs the committed baseline")
    args = ap.parse_args()
    main(quick=args.quick, gate=args.gate)
