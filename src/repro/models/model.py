"""Top-level models: layer schedules, parameter/cache schemas, forwards.

A model is a list of *segments*; each segment is ``count`` layers of one
block kind. Homogeneous segments are scanned (``lax.scan`` over stacked
params — one traced body regardless of depth); heterogeneous layers
(deepseek's dense layer 0, hymba's 3 global-attention layers) break the
stack into segments. A ``layer_pattern`` (Nemotron-H) gives one segment a
run of equal block kinds. Caches mirror the segment structure; a decode step
writes a segment's new K/V rows into its stacked cache in place where
``decode_writes_in_place`` allows, and rewrites each layer's cache with a
masked select elsewhere.

Three entry points per model — ``forward_train``, ``forward_prefill``,
``forward_decode`` (= serve_step's body) — all pure functions of
(params, inputs), jit/pjit-ready.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    PATTERN_KINDS, ModelConfig, RunConfig, ShapeConfig,
)
from repro.distribution.sharding import (
    ParamDesc, ShardingCtx, abstract_params, init_params, param_shardings,
)
from repro.models import blocks as blocks_mod
from repro.models.attention import decode_kernel_applies
from repro.models.blocks import apply_block, block_cache_schema, block_schema
from repro.models.layers import (
    apply_norm, embed_schema, embed_tokens, lm_logits, norm_schema,
    sinusoid_positions,
)


@dataclass(frozen=True)
class Segment:
    kind: str
    count: int
    scanned: bool
    window: int = 0       # 0 = full attention


def build_schedule(cfg: ModelConfig) -> Tuple[Segment, ...]:
    if cfg.layer_pattern:
        # one segment a run of equal letters (Nemotron-H's alternate, so
        # each block is a segment of one, unrolled)
        return tuple(Segment(PATTERN_KINDS[c], len(list(run)), True)
                     for c, run in itertools.groupby(cfg.blocks))
    if cfg.family == "ssm":
        return (Segment("ssm", cfg.num_layers, True),)
    if cfg.family == "encdec":
        return (Segment("dec", cfg.num_layers, True),)
    if cfg.family == "hybrid":
        segs: List[Segment] = []
        i = 0
        while i < cfg.num_layers:
            if i in cfg.global_attn_layers:
                segs.append(Segment("hybrid", 1, False, window=0))
                i += 1
            else:
                j = i
                while j < cfg.num_layers and j not in cfg.global_attn_layers:
                    j += 1
                segs.append(Segment("hybrid", j - i, True,
                                    window=cfg.attn_window))
                i = j
        return tuple(segs)
    if cfg.moe is not None:
        segs = []
        if cfg.dense_layer_prefix:
            segs.append(Segment("dense_prefix", cfg.dense_layer_prefix, False))
        segs.append(Segment("moe", cfg.num_layers - cfg.dense_layer_prefix, True))
        return tuple(segs)
    return (Segment("dense", cfg.num_layers, True),)


def _stack_schema(schema, count: int):
    return jax.tree.map(
        lambda d: dataclasses.replace(d, shape=(count,) + d.shape,
                                      dims=("layers",) + d.dims),
        schema, is_leaf=lambda x: isinstance(x, ParamDesc))


def model_schema(cfg: ModelConfig, mesh) -> Dict:
    s: Dict = {"embed": embed_schema(cfg.vocab_size, cfg.d_model,
                                     cfg.param_dtype, cfg.tie_embeddings),
               "final_norm": norm_schema(cfg.d_model, cfg.norm, cfg.param_dtype)}
    # every segment's params are stacked over its layers (leading dim =
    # count), scanned or not; unscanned segments index into the stack.
    s["segments"] = tuple(
        _stack_schema(block_schema(cfg, mesh, seg.kind), seg.count)
        for seg in build_schedule(cfg))
    if cfg.encoder_layers:
        enc = {"segments": (_stack_schema(block_schema(cfg, mesh, "enc"),
                                          cfg.encoder_layers),),
               "final_norm": norm_schema(cfg.d_model, cfg.norm, cfg.param_dtype)}
        s["encoder"] = enc
    return s


def cache_schema(cfg: ModelConfig, batch: int, max_seq: int,
                 dtype: str = "bfloat16") -> Tuple:
    """Each segment's cache descriptors, stacked over its layers; None for
    a segment that keeps none (an expert layer of a layer pattern)."""
    segs = []
    for seg in build_schedule(cfg):
        sch = block_cache_schema(cfg, seg.kind, batch, max_seq, seg.window,
                                 dtype)
        segs.append(_stack_schema(sch, seg.count) if sch else None)
    return tuple(segs)


# ---------------------------------------------------------------------------
# Segment runner (scan or unroll)
# ---------------------------------------------------------------------------


def _remat(fn, rcfg):
    if rcfg.remat == "none":
        return fn
    pol = (jax.checkpoint_policies.nothing_saveable if rcfg.remat == "full"
           else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=pol)


def _scanned(seg: Segment, rcfg) -> bool:
    return seg.scanned and seg.count > 1 and not rcfg.force_unroll_segments


def decode_writes_in_place(seg: Segment, cache_seg, shd: ShardingCtx,
                           rcfg) -> bool:
    """Whether a decode step writes this segment's new cache rows into its
    stacked cache in place.

    Yes for a linear (non-ring) k/v cache or an MLA latent cache, on no
    mesh or one whose ``model`` axis is 1: a scanned segment carries the
    stack in the scan's carry, an unrolled one (deepseek's single dense
    layer) threads it through its layers, so the cache that leaves is the
    donated one that entered. Everywhere else (ring windows,
    cross-attention or SSM state in the cache, a model-sharded sequence
    axis) each layer's cache is rewritten with the masked select of
    ``attention.write_decode_rows``. ``cache_seg``: the segment's cache
    leaves, arrays or schema descriptors (only shapes are read).
    """
    if cache_seg is None or set(cache_seg) not in ({"k", "v"}, {"lat"}):
        return False
    ring = "k" in cache_seg and bool(seg.window) \
        and cache_seg["k"].shape[2] <= seg.window
    return not ring and (shd.mesh is None or shd.tp == 1)


def decode_kernel_layers(cfg: ModelConfig, shd: ShardingCtx, rcfg,
                         max_seq: int, platform: str) -> int:
    """Layers whose decode attends with the flash-decode kernel in a
    program that lowers for ``platform``: on a TPU, those of the segments
    that write a stacked K/V cache in place (``decode_writes_in_place``)
    where ``attention.decode_kernel_applies``; latent (MLA) caches, ring
    windows, cross-attention and SSM state keep the jnp attention, as does
    every layer elsewhere."""
    if platform != "tpu":
        return 0
    return sum(
        seg.count for seg, c in zip(build_schedule(cfg),
                                    cache_schema(cfg, 1, max_seq))
        if c is not None and set(c) == {"k", "v"}
        and decode_writes_in_place(seg, c, shd, rcfg)
        and decode_kernel_applies(cfg, shd, seg.window, max_seq))


def run_segment(seg: Segment, p_seg, x, cfg, shd, rcfg, *, mode,
                positions=None, cache_seg=None, decode_pos=None, enc_out=None):
    """Returns (x, new_cache_seg, aux)."""

    def body(xc, per):
        p_l, c_l = per
        y, c2, aux = apply_block(p_l, xc, cfg, shd, rcfg, seg.kind,
                                 positions=positions, window=seg.window,
                                 cache=c_l, decode_pos=decode_pos,
                                 enc_out=enc_out, mode=mode)
        return y, (c2, aux)

    def body_in_place(carry, per):
        xc, c = carry
        p_l, layer = per
        y, c, aux = apply_block(p_l, xc, cfg, shd, rcfg, seg.kind,
                                positions=positions, window=seg.window,
                                cache=c, decode_pos=decode_pos, mode=mode,
                                cache_layer=layer)
        return (y, c), aux

    def over_layers(auxs):
        # integer aux are counts and add up over the layers; the rest
        # (losses, fractions) are averaged
        return ({k: jnp.sum(a, 0) if jnp.issubdtype(a.dtype, jnp.integer)
                 else jnp.mean(a, 0) for k, a in auxs.items()}
                if auxs else {})

    in_place = mode == "decode" and decode_writes_in_place(
        seg, cache_seg, shd, rcfg)
    if in_place and _scanned(seg, rcfg):
        (x, caches), auxs = jax.lax.scan(
            _remat(body_in_place, rcfg), (x, cache_seg),
            (p_seg, jnp.arange(seg.count)))
        return x, caches, over_layers(auxs)
    if _scanned(seg, rcfg):
        x, (caches, auxs) = jax.lax.scan(
            _remat(body, rcfg), x, (p_seg, cache_seg))
        return x, caches, over_layers(auxs)
    # unrolled (heterogeneous or single-layer segments; params still
    # stacked); in place, the stacked cache threads through the layers
    new_caches = []
    per_layer: List[Dict] = []
    c = cache_seg
    for i in range(seg.count):
        p_l = jax.tree.map(lambda a: a[i], p_seg)
        if in_place:
            (x, c), aux = _remat(body_in_place, rcfg)((x, c), (p_l, i))
        else:
            c_l = (jax.tree.map(lambda a: a[i], cache_seg)
                   if cache_seg is not None else None)
            x, (c2, aux) = _remat(body, rcfg)(x, (p_l, c_l))
            new_caches.append(c2)
        per_layer.append(aux or {})
    auxs = (jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
            if per_layer and per_layer[0] else {})
    if in_place:
        return x, c, over_layers(auxs)
    nc = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
          if new_caches and new_caches[0] else None)
    return x, nc, over_layers(auxs)


# ---------------------------------------------------------------------------
# Forwards
# ---------------------------------------------------------------------------


def encode(params, frames, cfg: ModelConfig, shd: ShardingCtx, rcfg):
    """Whisper encoder over stub frame embeddings (B, enc_seq, D)."""
    pos = jnp.arange(frames.shape[1])
    x = frames + sinusoid_positions(pos, cfg.d_model)[None].astype(frames.dtype)
    x = shd.constrain_act(x)
    enc = params["encoder"]
    seg = Segment("enc", cfg.encoder_layers, True)
    x, _, _ = run_segment(seg, enc["segments"][0], x, cfg, shd, rcfg,
                          mode="train", positions=pos)
    return apply_norm(enc["final_norm"], x, cfg.norm, cfg.norm_eps)


def _embed_in(params, tokens, cfg, shd):
    x = embed_tokens(params["embed"], tokens, jnp.dtype(cfg.dtype))
    if cfg.family == "encdec":   # sinusoidal decoder positions (stub choice)
        pos = jnp.arange(tokens.shape[1])
        x = x + sinusoid_positions(pos, cfg.d_model)[None].astype(x.dtype)
    return shd.constrain_act(x)


def forward_train(params, batch: Dict, cfg: ModelConfig, shd: ShardingCtx,
                  rcfg: RunConfig):
    """batch: tokens (B,S) [+ frames for encdec]. Returns (logits, aux)."""
    tokens = batch["tokens"]
    x = _embed_in(params, tokens, cfg, shd)
    positions = jnp.arange(tokens.shape[1])
    enc_out = None
    if cfg.encoder_layers:
        enc_out = encode(params, batch["frames"], cfg, shd, rcfg)
    aux_all: Dict = {}
    for seg, p_seg in zip(build_schedule(cfg), params["segments"]):
        x, _, aux = run_segment(seg, p_seg, x, cfg, shd, rcfg, mode="train",
                                positions=positions, enc_out=enc_out)
        for k, v in (aux or {}).items():
            aux_all[k] = aux_all.get(k, 0.0) + v
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = lm_logits(params["embed"], x, shd, cfg.logit_softcap)
    return logits, aux_all


def _to_ring(cache_leaf_kv, window: int, seq: int):
    """Convert full prefill k/v (B,S,...) to ring layout (B,W,...)."""
    w = min(window, seq)
    tail = cache_leaf_kv[:, -w:]
    r = seq % w
    if r:
        tail = jnp.roll(tail, r, axis=1)
    return tail


def _add_counts(total: Dict, aux: Dict) -> Dict:
    """Sum the integer aux (counts, e.g. an expert layer's routed
    assignments) of one segment into ``total``."""
    for k, v in (aux or {}).items():
        if jnp.issubdtype(v.dtype, jnp.integer):
            total[k] = total[k] + v if k in total else v
    return total


def forward_prefill(params, tokens, cfg: ModelConfig, shd: ShardingCtx,
                    rcfg: RunConfig, *, max_seq: int, frames=None,
                    cache_dtype: str = "bfloat16", return_counts=False):
    """Full-sequence prefill. Returns (last_logits (B,V), caches), and
    with ``return_counts`` the layers' counts (``_add_counts``) third."""
    b, s = tokens.shape
    x = _embed_in(params, tokens, cfg, shd)
    positions = jnp.arange(s)
    enc_out = None
    if cfg.encoder_layers:
        enc_out = encode(params, frames, cfg, shd, rcfg)
    schedule = build_schedule(cfg)
    caches_out = []
    counts: Dict = {}
    for seg, p_seg in zip(schedule, params["segments"]):
        x, cache_new, aux = run_segment(seg, p_seg, x, cfg, shd, rcfg,
                                        mode="prefill", positions=positions,
                                        enc_out=enc_out,
                                        cache_seg=_prefill_cache_placeholder(
                                            cfg, seg, b, cache_dtype))
        caches_out.append(_finalize_prefill_cache(
            cache_new, cfg, seg, s, max_seq, cache_dtype))
        _add_counts(counts, aux)
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = lm_logits(params["embed"], x[:, -1:], shd, cfg.logit_softcap)
    if return_counts:
        return logits[:, 0], tuple(caches_out), counts
    return logits[:, 0], tuple(caches_out)


def _prefill_cache_placeholder(cfg, seg: Segment, batch: int, dtype: str):
    """SSM blocks need a cache arg at prefill to emit their streaming state."""
    if seg.kind not in ("ssm", "hybrid"):
        return None
    sch = block_cache_schema(cfg, seg.kind, batch, 1, seg.window, dtype)
    sch = {k: v for k, v in sch.items()
           if k in ("state", "conv_x", "conv_B", "conv_C")}
    one = init_params(_stack_schema(sch, seg.count), jax.random.PRNGKey(0))
    return one


def _finalize_prefill_cache(cache_new, cfg, seg: Segment, s: int,
                            max_seq: int, dtype: str):
    """Pad/convert prefill caches to their decode-time layout.

    All cache leaves are stacked over the segment's layers: seq axis = 2.
    Window segments convert to the ring layout; full-attention/MLA caches
    are zero-padded out to ``max_seq`` decode slots.
    """
    if cache_new is None:
        return None
    out = {}
    for k, v in cache_new.items():
        if k in ("k", "v") and seg.window and seg.window < max_seq:
            out[k] = _to_ring_stacked(v, seg.window, s)
        elif k in ("k", "v", "lat"):
            pad = max_seq - s
            if pad > 0:
                width = [(0, 0)] * v.ndim
                width[2] = (0, pad)
                v = jnp.pad(v, width)
            out[k] = v
        else:
            out[k] = v
    return out


def _to_ring_stacked(v, window, s):
    # v: (L, B, S, ...) stacked over layers -> (L, B, W, ...) ring layout
    w = min(window, s)
    tail = v[:, :, -w:]
    r = s % w
    if r:
        tail = jnp.roll(tail, r, axis=2)
    return tail


def forward_decode(params, caches, tokens, pos, cfg: ModelConfig,
                   shd: ShardingCtx, rcfg: RunConfig, return_counts=False):
    """One decode step. tokens: (B,1); pos: (B,). Returns (logits, caches'),
    and with ``return_counts`` the layers' counts (``_add_counts``) third."""
    x = embed_tokens(params["embed"], tokens, jnp.dtype(cfg.dtype))
    if cfg.family == "encdec":
        x = x + jax.vmap(lambda p: sinusoid_positions(p, cfg.d_model))(
            pos)[:, None].astype(x.dtype)
    x = shd.constrain_act(x)
    new_caches = []
    counts: Dict = {}
    for seg, p_seg, c_seg in zip(build_schedule(cfg), params["segments"], caches):
        x, c2, aux = run_segment(seg, p_seg, x, cfg, shd, rcfg,
                                 mode="decode", positions=pos,
                                 cache_seg=c_seg, decode_pos=pos)
        new_caches.append(c2)
        _add_counts(counts, aux)
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = lm_logits(params["embed"], x, shd, cfg.logit_softcap)
    if return_counts:
        return logits[:, 0], tuple(new_caches), counts
    return logits[:, 0], tuple(new_caches)


# ---------------------------------------------------------------------------
# Abstract inputs per (cfg, shape): the dry-run contract
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh=None,
                cache_dtype: str = "bfloat16") -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    out: Dict = {}
    if shape.kind == "train":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        out["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        if cfg.encoder_layers:
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    elif shape.kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        if cfg.encoder_layers:
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    else:  # decode
        out["tokens"] = jax.ShapeDtypeStruct((b, 1), i32)
        out["pos"] = jax.ShapeDtypeStruct((b,), i32)
        out["caches"] = abstract_params(cache_schema(cfg, b, s, cache_dtype))
    return out


def build_params(cfg: ModelConfig, mesh, key=None, abstract=False):
    schema = model_schema(cfg, mesh)
    if abstract:
        return abstract_params(schema)
    return init_params(schema, key if key is not None else jax.random.PRNGKey(0))
