"""Model assembly: params, forward, loss, prefill, decode — all families.

The layer stack is a ``lax.scan`` over *layer groups* with stacked params
(leading ``layers`` dim).  Grouping (see repro.models.blocks) encodes
heterogeneous stacks without lax.cond:

  dense/moe/vlm/audio  group = {"blk": layer}          n_groups = L
  gemma2               group = {"sub0": local, "sub1": global}  L/2
  ssm                  group = {"blk": mamba}          L
  hybrid (zamba2)      group = {"mamba": [P x mamba]} + closure-shared
                       transformer block applied once per group

Modes: train (loss), prefill (emit cache), decode (one token vs cache).
Caches mirror the group tree; sliding-window sites allocate only
min(S, window) slots (rolling layout).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import blocks
from repro.models.blocks import Ctx
from repro.models.config import ArchConfig, ShapeConfig
from repro.models.layers import norm_decl, rmsnorm
from repro.models.moe import moe_ffn
from repro.models.params import (ParamDecl, abstract_params, default_rules,
                                 init_params, is_decl, param_specs)

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter declarations.
# ---------------------------------------------------------------------------


def _stack(decls: PyTree, n: int) -> PyTree:
    return jax.tree_util.tree_map(
        lambda d: ParamDecl((n,) + d.shape, ("layers",) + d.logical,
                            d.dtype, d.init,
                            d.fan_in or (d.shape[-2] if len(d.shape) >= 2
                                         else d.shape[-1])),
        decls, is_leaf=is_decl)


def n_groups(cfg: ArchConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.hybrid_period
    if cfg.local_global_period == 2:
        return cfg.n_layers // 2
    return cfg.n_layers


def group_decls(cfg: ArchConfig) -> PyTree:
    if cfg.family == "ssm":
        per = {"blk": blocks.mamba_decls(cfg)}
    elif cfg.family == "hybrid":
        per = {"mamba": _stack(blocks.mamba_decls(cfg), cfg.hybrid_period)}
    elif cfg.local_global_period == 2:
        per = {"sub0": blocks.transformer_decls(cfg, cfg.moe is not None),
               "sub1": blocks.transformer_decls(cfg, cfg.moe is not None)}
    else:
        per = {"blk": blocks.transformer_decls(cfg, cfg.moe is not None)}
    return _stack(per, n_groups(cfg))


def param_decls(cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    decls: Dict[str, Any] = {}
    if cfg.n_codebooks:
        decls["embed"] = ParamDecl((cfg.n_codebooks, cfg.vocab, d),
                                   (None, "vocab", "embed"))
        decls["out_heads"] = ParamDecl((cfg.n_codebooks, d, cfg.vocab),
                                       (None, "embed", "vocab"))
    else:
        decls["embed"] = ParamDecl((cfg.vocab, d), ("vocab", "embed"))
        if not cfg.tie_embeddings:
            decls["lm_head"] = ParamDecl((d, cfg.vocab), ("embed", "vocab"))
    decls["final_norm"] = (ParamDecl((d,), ("embed",), init="zeros")
                           if cfg.post_norms else norm_decl(d))
    decls["layers"] = group_decls(cfg)
    if cfg.family == "hybrid":
        decls["shared"] = blocks.transformer_decls(cfg, use_moe=False)
    return decls


def init(cfg: ArchConfig, key) -> PyTree:
    return init_params(param_decls(cfg), key)


def abstract(cfg: ArchConfig) -> PyTree:
    return abstract_params(param_decls(cfg))


def specs(cfg: ArchConfig, mesh_axis_names, axis_sizes=None) -> PyTree:
    return param_specs(param_decls(cfg), default_rules(mesh_axis_names),
                       axis_sizes)


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# ---------------------------------------------------------------------------
# Sharding helper threaded through blocks via Ctx.shard.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Shardings:
    mesh: Optional[Mesh] = None
    #: sequence parallelism for the residual stream: shard the seq dim of
    #: [B, S, D] activations over the TP axis between blocks, turning the
    #: per-layer TP all-reduces into reduce-scatter + all-gather pairs
    #: (half the bytes) — a §Perf hillclimb knob.
    seq_shard: bool = False
    #: shard attention heads over the TP axis.  With head counts that do
    #: not divide 16 (qwen2: H=28, kv=4) the padded uneven sharding makes
    #: GSPMD re-gather score-shaped f32 blocks in the attention backward
    #: (~1.3 TB/step measured) — turning this OFF replicates the (cheap)
    #: attention math over TP and deletes those collectives.
    attn_heads_shard: bool = True

    def act_rules(self) -> Dict[str, Any]:
        if self.mesh is None:
            return {}
        names = self.mesh.axis_names
        fsdp = tuple(a for a in ("pod", "data") if a in names) or None
        tp = "model" if "model" in names else None
        htp = tp if self.attn_heads_shard else None
        return {"batch": fsdp, "heads": htp, "kv": htp, "vocab": tp,
                "mlp_act": tp, "embed_act": None, "seq": fsdp,
                "seq_res": tp if self.seq_shard else None}

    def shard(self, x: jnp.ndarray, logical: Tuple) -> jnp.ndarray:
        if self.mesh is None:
            return x
        rules = self.act_rules()
        used: set = set()
        axes = []
        for name in logical:
            mapped = rules.get(name) if name else None
            if mapped is not None:
                flat = (mapped,) if isinstance(mapped, str) else tuple(mapped)
                if any(a in used for a in flat):
                    mapped = None
                else:
                    used.update(flat)
            axes.append(mapped)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, P(*axes)))

    def moe_wrapper(self, cfg: ArchConfig) -> Optional[Callable]:
        """shard_map'd MoE so dispatch stays local per data shard."""
        if self.mesh is None or cfg.moe is None:
            return None
        names = self.mesh.axis_names
        dp = tuple(a for a in ("pod", "data") if a in names)
        tp = "model" if "model" in names else None
        # ZeRO gather must cover EVERY axis the embed (D) dim is stored
        # over — ("pod", "data") on the multi-pod mesh.
        zero = dp or None
        rules = default_rules(names)
        from repro.models.moe import moe_decls as _md
        sizes = mesh_axis_sizes(self.mesh)
        pspecs = param_specs(_md(cfg.d_model, cfg.moe), rules, sizes)

        # checkpoint INSIDE the shard_map: outer remat does not reach
        # through shard_map, so without this the f32 combine output is
        # saved per layer (5+ GB/device at 56 layers).
        @jax.checkpoint
        def body(x2d, prm):
            return moe_ffn(x2d, prm, cfg.moe, tp_axis=tp, zero_axes=zero)

        dp_n = 1
        sizes_ = mesh_axis_sizes(self.mesh)
        for a in dp:
            dp_n *= sizes_[a]

        def build(token_spec):
            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(token_spec, pspecs),
                out_specs=token_spec, check_vma=False)

        sharded = build(P(dp if dp else None, None))
        replicated = build(P(None, None))

        def fn(x2d, prm):
            # decode at global_batch < dp (long_500k): tokens cannot split
            # over the data axes — run the (tiny) batch replicated.
            if x2d.shape[0] % max(dp_n, 1) == 0 and x2d.shape[0] >= dp_n:
                return sharded(x2d, prm)
            return replicated(x2d, prm)

        return fn


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------


def _embed(cfg: ArchConfig, params: PyTree, batch: Dict[str, jnp.ndarray],
           ctx: Ctx) -> jnp.ndarray:
    tokens = batch["tokens"]
    if cfg.n_codebooks:
        # tokens [B, S, CB]: summed codebook embeddings (EnCodec stub).
        h = jnp.zeros(tokens.shape[:2] + (cfg.d_model,), jnp.bfloat16)
        for cb in range(cfg.n_codebooks):
            h = h + params["embed"][cb][tokens[..., cb]]
    else:
        h = params["embed"][tokens]
    if cfg.vision_tokens and not ctx.decode and "vision" in batch:
        v = batch["vision"].astype(h.dtype)          # [B, V, D] (stub)
        h = jnp.concatenate([v, h[:, v.shape[1]:, :]], axis=1)
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    return ctx.shard(h, ("batch", "seq_res", "embed_act"))


def _group_body(cfg: ArchConfig, ctx: Ctx, shared_params):
    """Returns body(h, (gparams, gcache)) -> (h, new_gcache)."""

    def body(h, xs):
        gp, gcache = xs

        def site(name, fn, *args):
            c = None if gcache is None else gcache[name]
            out, nc = fn(*args, cache=c)
            return out, nc

        ncache = {}
        if cfg.family == "ssm":
            h, nc = site("blk", lambda cache: blocks.apply_mamba_layer(
                gp["blk"], h, ctx, cache=cache))
            ncache["blk"] = nc
        elif cfg.family == "hybrid":
            h, nc = site("shared", lambda cache: blocks.apply_transformer_layer(
                shared_params, h, ctx, window=None, cache=cache))
            ncache["shared"] = nc

            def inner(hc, ixs):
                ip, icache = ixs
                hh, inc = blocks.apply_mamba_layer(ip, hc, ctx, cache=icache)
                return hh, inc

            inner_cache = None if gcache is None else gcache["mamba"]
            h, mcaches = jax.lax.scan(
                inner, h, (gp["mamba"], inner_cache))
            ncache["mamba"] = mcaches
        elif cfg.local_global_period == 2:
            h, nc0 = site("sub0", lambda cache: blocks.apply_transformer_layer(
                gp["sub0"], h, ctx, window=cfg.window, cache=cache))
            h, nc1 = site("sub1", lambda cache: blocks.apply_transformer_layer(
                gp["sub1"], h, ctx, window=None, cache=cache))
            ncache["sub0"], ncache["sub1"] = nc0, nc1
        else:
            h, nc = site("blk", lambda cache: blocks.apply_transformer_layer(
                gp["blk"], h, ctx, window=cfg.window, cache=cache))
            ncache["blk"] = nc
        if ctx.mode == "train":
            return h, None
        return h, ncache

    return body


def _remat_wrap(cfg: ArchConfig, body):
    if cfg.remat == "none":
        return body
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(body, policy=policy)
    return jax.checkpoint(body)        # "full": save only the carry


def run_layers(cfg: ArchConfig, params: PyTree, h: jnp.ndarray, ctx: Ctx,
               cache: Optional[PyTree] = None
               ) -> Tuple[jnp.ndarray, Optional[PyTree]]:
    shared = params.get("shared")
    body = _group_body(cfg, ctx, shared)
    if ctx.mode == "train":
        # checkpoint the EXACT callable handed to scan — jax's
        # remat-in-scan handling keys on the scan body itself; a thin
        # lambda around a checkpointed inner function left extra f32
        # residuals stacked per layer.
        def scan_body(c, gp):
            return body(c, (gp, None))
        h, _ = jax.lax.scan(_remat_wrap(cfg, scan_body), h,
                            params["layers"])
        return h, None
    if ctx.mode == "decode":
        # Thread the cache through the scan CARRY with per-layer dynamic
        # read/write: while-loop carries update in place (the donated
        # input buffer is reused), whereas a cache passed as xs -> ys
        # made XLA materialize a second full cache as a temp.
        def dec_body(carry, gp):
            h_c, cache_c, i = carry
            gcache = jax.tree_util.tree_map(
                lambda buf: jax.lax.dynamic_index_in_dim(
                    buf, i, 0, keepdims=False), cache_c)
            h_c, ncache = body(h_c, (gp, gcache))
            cache_c = jax.tree_util.tree_map(
                lambda buf, nc: jax.lax.dynamic_update_index_in_dim(
                    buf, nc.astype(buf.dtype), i, 0), cache_c, ncache)
            return (h_c, cache_c, i + 1), None

        (h, cache, _), _ = jax.lax.scan(
            dec_body, (h, cache, jnp.int32(0)), params["layers"])
        return h, cache
    h, new_cache = jax.lax.scan(body, h, (params["layers"], cache))
    return h, new_cache


def logits_fn(cfg: ArchConfig, params: PyTree, h: jnp.ndarray,
              ctx: Ctx) -> jnp.ndarray:
    hn = rmsnorm(h, params["final_norm"], cfg.norm_eps,
                 gemma_style=cfg.post_norms)
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,cdv->bscv", hn, params["out_heads"])
        logical = ("batch", None, None, "vocab")
    elif cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", hn, params["embed"])
        logical = ("batch", None, "vocab")
    else:
        logits = hn @ params["lm_head"]
        logical = ("batch", None, "vocab")
    if cfg.final_softcap > 0.0:
        logits = (cfg.final_softcap
                  * jnp.tanh(logits.astype(jnp.float32) / cfg.final_softcap)
                  ).astype(logits.dtype)
    return ctx.shard(logits, logical)


def make_ctx(cfg: ArchConfig, mode: str, sh: Shardings,
             pos: Optional[jnp.ndarray] = None,
             skip_masked_blocks: bool = False,
             block_q: int = 256, block_k: int = 256,
             kv_quant: bool = False) -> Ctx:
    return Ctx(cfg=cfg, mode=mode, pos=pos, shard=sh.shard,
               block_q=block_q, block_k=block_k,
               skip_masked_blocks=skip_masked_blocks,
               moe_shard_map=sh.moe_wrapper(cfg), kv_quant=kv_quant)


def forward(cfg: ArchConfig, params: PyTree, batch: Dict[str, jnp.ndarray],
            ctx: Ctx) -> jnp.ndarray:
    h = _embed(cfg, params, batch, ctx)
    h, _ = run_layers(cfg, params, h, ctx)
    return logits_fn(cfg, params, h, ctx)


# ---------------------------------------------------------------------------
# Loss (causal LM; labels provided shifted by the data pipeline).
# ---------------------------------------------------------------------------


def xent(logits: jnp.ndarray, labels: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Sharded-vocab-safe cross entropy: one-hot dot, no gather."""
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, vocab, dtype=jnp.float32)
    ll = jnp.sum(onehot * lf, axis=-1)
    return (lse - ll).mean()


def loss_fn(cfg: ArchConfig, params: PyTree, batch: Dict[str, jnp.ndarray],
            ctx: Ctx) -> jnp.ndarray:
    logits = forward(cfg, params, batch, ctx)
    return xent(logits, batch["labels"], cfg.vocab)


# ---------------------------------------------------------------------------
# Serving: cache construction, prefill, decode.
# ---------------------------------------------------------------------------


def _site_cache_shape(cfg: ArchConfig, batch: int, seq: int,
                      window: Optional[int],
                      quant: bool = False) -> Dict[str, Tuple]:
    keep = min(seq, window) if window else seq
    kv = (batch, keep, cfg.n_kv, cfg.head_dim)
    if quant:
        sc = (batch, keep, cfg.n_kv, 1)
        return {"k": kv, "v": kv, "ks": sc, "vs": sc}
    return {"k": kv, "v": kv}


def _mamba_cache_shape(cfg: ArchConfig, batch: int) -> Dict[str, Tuple]:
    s = cfg.ssm
    conv_dim = s.d_inner + 2 * s.n_groups * s.d_state
    return {"state": (batch, s.n_heads, s.d_state, s.head_dim),
            "conv": (batch, s.d_conv - 1, conv_dim)}


def cache_struct(cfg: ArchConfig, batch: int, seq: int,
                 dtype=jnp.bfloat16, quant: bool = False) -> PyTree:
    """Shape tree of the decode cache (leading dim = n_groups).

    quant=True stores KV in int8 with f32 per-(token, head) scales —
    halves (vs bf16) the dominant serving buffer; required to fit the MHA
    (kv=40) 32k x 128 cache on a single pod."""
    g = n_groups(cfg)
    f32 = jnp.float32
    kv_dt = jnp.int8 if quant else dtype

    def kv_site(sh: Dict[str, Tuple]) -> Dict[str, Tuple]:
        return {k: ((g,) + v, f32 if k in ("ks", "vs") else kv_dt)
                for k, v in sh.items()}

    if cfg.family == "ssm":
        sh = _mamba_cache_shape(cfg, batch)
        tree = {"blk": {"state": ((g,) + sh["state"], f32),
                        "conv": ((g,) + sh["conv"], dtype)}}
    elif cfg.family == "hybrid":
        p = cfg.hybrid_period
        msh = _mamba_cache_shape(cfg, batch)
        tree = {"shared": kv_site(_site_cache_shape(cfg, batch, seq, None,
                                                    quant)),
                "mamba": {"state": ((g, p) + msh["state"], f32),
                          "conv": ((g, p) + msh["conv"], dtype)}}
    elif cfg.local_global_period == 2:
        tree = {"sub0": kv_site(_site_cache_shape(cfg, batch, seq,
                                                  cfg.window, quant)),
                "sub1": kv_site(_site_cache_shape(cfg, batch, seq, None,
                                                  quant))}
    else:
        tree = {"blk": kv_site(_site_cache_shape(cfg, batch, seq,
                                                 cfg.window, quant))}
    return tree


def _cache_leaf(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[0], tuple)


def cache_abstract(cfg: ArchConfig, batch: int, seq: int,
                   dtype=jnp.bfloat16, quant: bool = False) -> PyTree:
    return jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(*sd),
        cache_struct(cfg, batch, seq, dtype, quant), is_leaf=_cache_leaf)


def cache_init(cfg: ArchConfig, batch: int, seq: int,
               dtype=jnp.bfloat16, quant: bool = False) -> PyTree:
    return jax.tree_util.tree_map(
        lambda sd: jnp.zeros(*sd),
        cache_struct(cfg, batch, seq, dtype, quant), is_leaf=_cache_leaf)


def cache_specs(cfg: ArchConfig, mesh: Mesh, global_batch: int,
                seq_len: int, quant: bool = False) -> PyTree:
    """PartitionSpecs for the decode cache.

    Batch shards over the fsdp axes when divisible; otherwise (long_500k,
    global_batch=1) the KV *sequence* dim carries the fsdp shard (SP).
    KV heads shard on the TP axis when divisible; when NOT divisible
    (GQA kv=2..8 < 16-way TP) the *sequence* dim takes the model axis
    instead — flash-decoding style sequence-parallel attention, where
    GSPMD turns the softmax statistics and the p@V contraction into small
    per-layer all-reduces.  jit in_shardings require exact divisibility,
    so every mapping is divisibility-checked here."""
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in fsdp:
        dp *= sizes[a]
    batch_ok = bool(fsdp) and dp > 1 and global_batch % dp == 0
    bax = fsdp if batch_ok else None
    tpn = sizes.get("model", 1)
    has_tp = "model" in names

    def tp_if(div: int):
        return "model" if (has_tp and div % tpn == 0 and div > 0) else None

    def kv_spec(site_window) -> Dict[str, P]:      # [g, B, S, G, hd]
        keep = min(seq_len, site_window) if site_window else seq_len
        kvp = tp_if(cfg.n_kv)
        seq_parts = [] if batch_ok else list(fsdp)
        if kvp is None and has_tp:
            seq_parts.append("model")              # flash-decode SP
        prod = 1
        for a in seq_parts:
            prod *= sizes[a]
        seq_ax = tuple(seq_parts) if (seq_parts and keep % prod == 0) \
            else None
        spec = P(None, bax, seq_ax, kvp, None)
        out = {"k": spec, "v": spec}
        if quant:
            out["ks"] = spec
            out["vs"] = spec
        return out

    def mamba_spec(lead: int):         # state [*,B,H,N,P]; conv [*,B,K-1,C]
        s = cfg.ssm
        conv_dim = s.d_inner + 2 * s.n_groups * s.d_state
        pre = (None,) * lead
        return {"state": P(*pre, bax, tp_if(s.n_heads), None, None),
                "conv": P(*pre, bax, None, tp_if(conv_dim))}

    if cfg.family == "ssm":
        return {"blk": mamba_spec(1)}
    if cfg.family == "hybrid":
        return {"shared": kv_spec(None), "mamba": mamba_spec(2)}
    if cfg.local_global_period == 2:
        return {"sub0": kv_spec(cfg.window), "sub1": kv_spec(None)}
    return {"blk": kv_spec(cfg.window)}


def pad_cache(cfg: ArchConfig, cache: PyTree, max_seq: int) -> PyTree:
    """Grow a prefill cache to ``max_seq`` serving slots.

    KV sites pad the sequence dim (dim 2 of [g, B, S, G, hd]) up to
    min(max_seq, site window); appended slots are unwritten and the rolling
    position formula masks them until the stream reaches them.  SSM state /
    conv tails are length-independent and pass through.  No-op when the
    prefill already filled a window-limited site."""

    def pad_site(site: Dict[str, jnp.ndarray], window) -> Dict[str, jnp.ndarray]:
        out = {}
        for name, kv in site.items():
            target = min(max_seq, window) if window else max_seq
            padlen = target - kv.shape[2]
            if padlen > 0:
                pad = [(0, 0)] * kv.ndim
                pad[2] = (0, padlen)
                kv = jnp.pad(kv, pad)
            out[name] = kv
        return out

    if cfg.family == "ssm":
        return cache
    if cfg.family == "hybrid":
        return {"shared": pad_site(cache["shared"], None),
                "mamba": cache["mamba"]}
    if cfg.local_global_period == 2:
        return {"sub0": pad_site(cache["sub0"], cfg.window),
                "sub1": pad_site(cache["sub1"], None)}
    return {"blk": pad_site(cache["blk"], cfg.window)}


def prefill(cfg: ArchConfig, params: PyTree, batch: Dict[str, jnp.ndarray],
            ctx: Ctx) -> Tuple[jnp.ndarray, PyTree]:
    """Returns (last-position logits [B, V...], cache)."""
    h = _embed(cfg, params, batch, ctx)
    h, cache = run_layers(cfg, params, h, ctx)
    logits = logits_fn(cfg, params, h[:, -1:, :], ctx)
    return logits[:, 0], cache


def decode_step(cfg: ArchConfig, params: PyTree, cache: PyTree,
                tokens: jnp.ndarray, pos: jnp.ndarray, ctx: Ctx,
                vision: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, PyTree]:
    """One decode step.  tokens [B, 1] (audio: [B, 1, CB]); pos scalar."""
    batch = {"tokens": tokens}
    h = _embed(cfg, params, batch, ctx)
    h, new_cache = run_layers(cfg, params, h, ctx, cache=cache)
    logits = logits_fn(cfg, params, h, ctx)
    return logits[:, 0], new_cache
