"""Block composition: (attention | SSD | hybrid | enc | dec) + (MLP | MoE).

One ``apply_block`` entry point per layer, dispatched on a static ``kind``:

  dense         pre-norm attn + MLP                  (llama/internlm/nemotron/
                                                      granite/chameleon)
  moe           pre-norm attn + MoE (+shared/dense)  (arctic, deepseek body)
  dense_prefix  attn + dense MLP w/ prefix d_ff      (deepseek layer 0)
  ssm           Mamba-2 block only                   (mamba2, Nemotron-H's M)
  hybrid        parallel attn+SSD heads, then MLP    (hymba)
  attn          pre-norm attn only                   (Nemotron-H's *)
  experts       pre-norm MoE only                    (Nemotron-H's E)
  enc           bidirectional attn + MLP             (whisper encoder)
  dec           self-attn + cross-attn + MLP         (whisper decoder)

Attention flavor (GQA vs MLA) is chosen by the config. Caches are dicts whose
schema mirrors the block kind (see ``block_cache_schema``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distribution.sharding import ParamDesc, ShardingCtx
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import attn_schema, gqa_attention, mla_attention, mla_schema
from repro.models.layers import apply_mlp, apply_norm, mlp_schema, norm_schema
from repro.models.moe import apply_moe, moe_schema


def _is_mla(cfg: ModelConfig) -> bool:
    return cfg.mla is not None


def block_schema(cfg: ModelConfig, mesh, kind: str) -> Dict:
    d = cfg.d_model
    nk = cfg.norm
    pd = cfg.param_dtype
    s: Dict = {}
    if kind in ("dense", "moe", "dense_prefix", "enc", "dec", "hybrid",
                "attn"):
        s["ln1"] = norm_schema(d, nk, pd)
        s["attn"] = mla_schema(cfg, mesh) if _is_mla(cfg) else \
            attn_schema(cfg, mesh)
    if kind == "attn":
        return s
    if kind == "experts":
        return {"ln1": norm_schema(d, nk, pd), "moe": moe_schema(cfg, mesh)}
    if kind == "dec":
        s["ln_cross"] = norm_schema(d, nk, pd)
        s["cross"] = attn_schema(cfg, mesh, cross=True)
    if kind == "hybrid":
        s["ssm"] = ssm_mod.ssm_schema(cfg, mesh)
        s["attn_out_norm"] = norm_schema(d, nk, pd)
        s["ssm_out_norm"] = norm_schema(d, nk, pd)
    if kind == "ssm":
        s["ln1"] = norm_schema(d, nk, pd)
        s["ssm"] = ssm_mod.ssm_schema(cfg, mesh)
        return s
    # FFN half
    s["ln2"] = norm_schema(d, nk, pd)
    if kind == "moe":
        s["moe"] = moe_schema(cfg, mesh)
    elif kind == "dense_prefix":
        s["mlp"] = mlp_schema(d, cfg.dense_prefix_ff or cfg.d_ff,
                              cfg.activation, pd)
    else:
        s["mlp"] = mlp_schema(d, cfg.d_ff, cfg.activation, pd)
    return s


def block_cache_schema(cfg: ModelConfig, kind: str, batch: int, seq: int,
                       window: int, dtype: str) -> Dict:
    """Cache descriptors for one layer of this kind. ``seq`` = max positions;
    window layers keep a ring buffer of ``window`` slots."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    s: Dict = {}
    if kind in ("dense", "moe", "dense_prefix", "dec", "hybrid", "attn"):
        if _is_mla(cfg):
            r = attn_mod.mla_cache_width(cfg.mla)
            s["lat"] = ParamDesc((batch, seq, r), ("batch", "kv_seq", None),
                                 dtype, "zeros")
        else:
            n = min(seq, window) if window else seq
            dims = ("batch", "kv_seq", "kv_heads", "head_dim")
            s["k"] = ParamDesc((batch, n, kv, hd), dims, dtype, "zeros")
            s["v"] = ParamDesc((batch, n, kv, hd), dims, dtype, "zeros")
    if kind == "dec":
        dims = ("batch", None, "kv_heads", "head_dim")
        s["ck"] = ParamDesc((batch, cfg.encoder_seq, kv, hd), dims, dtype, "zeros")
        s["cv"] = ParamDesc((batch, cfg.encoder_seq, kv, hd), dims, dtype, "zeros")
    if kind in ("ssm", "hybrid"):
        s.update(ssm_mod.ssm_cache_schema(cfg, batch, dtype))
    return s


# ---------------------------------------------------------------------------


def _attn(p, x, cfg, shd, rcfg, **kw):
    if _is_mla(cfg):
        kw.pop("window", None)
        kw.pop("kv_x", None)
        kw.pop("causal", None)
        return mla_attention(p, x, cfg, shd, rcfg, **kw)
    return gqa_attention(p, x, cfg, shd, rcfg, **kw)


def apply_block(p, x, cfg: ModelConfig, shd: ShardingCtx, rcfg, kind: str, *,
                positions=None, window: int = 0, cache: Optional[Dict] = None,
                decode_pos=None, enc_out=None, mode: str = "train",
                cache_layer=None):
    """Returns (x', new_cache_or_None, aux_dict).

    ``cache_layer``: decode against a segment's stacked k/v or latent
    cache, this layer being that index (``gqa_attention``,
    ``mla_attention``). An ``experts`` block keeps no cache (None)."""
    aux: Dict = {}
    decode = mode == "decode"
    want_cache = mode in ("prefill", "decode")
    new_cache: Dict = {} if want_cache else None

    if kind == "ssm":
        h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
        y, c2 = ssm_mod.ssm_block(p["ssm"], h, cfg, shd, rcfg,
                                  cache=cache, decode=decode)
        x = x + y
        return x, c2, aux
    if kind == "experts":
        h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
        y, aux = apply_moe(p["moe"], h, cfg, shd, rcfg)
        return shd.constrain_act(x + y), None, aux

    # ---- attention half ----
    if kind in ("dense", "moe", "dense_prefix", "enc", "dec", "hybrid",
                "attn"):
        h = apply_norm(p["ln1"], x, cfg.norm, cfg.norm_eps)
        akw: Dict = dict(positions=positions, window=window,
                         causal=(kind != "enc"))
        if decode:
            akw.update(cache={k: cache[k] for k in ("k", "v", "lat")
                              if k in cache} if cache else None,
                       decode_pos=decode_pos)
            if cache_layer is not None:
                akw["cache_layer"] = cache_layer
        if want_cache and not decode:
            res = _attn(p["attn"], h, cfg, shd, rcfg, return_cache=True, **akw)
            a, ac = res
            if new_cache is not None:
                new_cache.update(ac)
        elif decode:
            a, ac = _attn(p["attn"], h, cfg, shd, rcfg, **akw)
            new_cache.update(ac)
        else:
            a = _attn(p["attn"], h, cfg, shd, rcfg, **akw)

        if kind == "hybrid":
            sc = None
            if cache is not None:
                sc = {k: cache[k] for k in
                      ("state", "conv_x", "conv_B", "conv_C")}
            sout, sc2 = ssm_mod.ssm_block(p["ssm"], h, cfg, shd, rcfg,
                                          cache=sc, decode=decode)
            a = 0.5 * (
                apply_norm(p["attn_out_norm"], a, cfg.norm, cfg.norm_eps)
                + apply_norm(p["ssm_out_norm"], sout, cfg.norm, cfg.norm_eps))
            if new_cache is not None and sc2 is not None:
                new_cache.update(sc2)
        x = x + a
    if kind == "attn":
        return shd.constrain_act(x), new_cache, aux

    # ---- cross attention (whisper decoder) ----
    if kind == "dec":
        h = apply_norm(p["ln_cross"], x, cfg.norm, cfg.norm_eps)
        if mode == "decode":
            c, _ = gqa_attention(p["cross"], h, cfg, shd, rcfg,
                                 positions=positions,
                                 cache={"k": cache["ck"], "v": cache["cv"]},
                                 return_cache=True, cross_decode=True)
            new_cache["ck"], new_cache["cv"] = cache["ck"], cache["cv"]
        elif mode == "prefill":
            c, cc = gqa_attention(p["cross"], h, cfg, shd, rcfg,
                                  positions=positions, kv_x=enc_out,
                                  return_cache=True)
            new_cache["ck"], new_cache["cv"] = cc["k"], cc["v"]
        else:
            c = gqa_attention(p["cross"], h, cfg, shd, rcfg,
                              positions=positions, kv_x=enc_out)
        x = x + c

    # ---- FFN half ----
    h = apply_norm(p["ln2"], x, cfg.norm, cfg.norm_eps)
    if kind == "moe":
        y, aux = apply_moe(p["moe"], h, cfg, shd, rcfg)
    else:
        y = apply_mlp(p["mlp"], h, cfg.activation, shd)
    x = x + y
    x = shd.constrain_act(x)
    return x, new_cache, aux
