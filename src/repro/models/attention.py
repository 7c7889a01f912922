"""Attention: GQA (blockwise training path + one-shot decode), windows, MLA.

Design notes (see DESIGN.md §4):

* **Head padding.** When Q-heads don't divide the model axis (llama 24,
  hymba 25, arctic 56, whisper 12 on a 16-way axis), ``padded_heads`` rounds
  the *parameter* head count up. The attention output is multiplied by a
  constant head mask before the out-projection, which provably zeroes both
  the padded heads' contribution and all gradients into their weights
  (masking at ``o`` kills both directions). Waste is reported honestly by the
  roofline "useful-FLOP ratio".

* **GQA mapping.** KV projections keep the true kv-head count (replicated
  over the model axis when kv < tp). Q-head h reads kv head ``map[h]``; the
  map handles padded heads arbitrarily (they are inert).

* **Training/prefill path** is a triangular blockwise (flash-style) softmax:
  python-unrolled q-block loop, each with a *static* kv-block scan range
  (causal and sliding-window limits are static), online (m, l, acc)
  accumulation, rematerialized body. No S^2 tensor is ever materialized and
  causal/window FLOPs are not wasted on masked-out blocks. On TPU the Pallas
  flash kernel (repro/kernels/flash_attention.py) implements this layout.

* **Decode path** is a one-shot masked softmax against the cache; the cache
  is sharded over the model axis on the *sequence* dim (context-parallel
  decode), so XLA lowers the max/sum reductions into the log-sum-exp
  combine across shards (the explicit shard_map variant lives in
  repro/serve/engine.py for the ring stack). Where a segment's stacked
  cache is written in place on one device and the program lowers for a
  TPU, the Pallas flash-decode kernel (repro/kernels/decode_attention.py)
  reads each slot's cache from the stack only up to its position
  (``decode_kernel_applies``).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.distribution.sharding import ParamDesc, ShardingCtx, padded_heads
from repro.kernels import decode_attention as flash_decode
from repro.models.layers import (
    apply_norm, apply_rope, f32, norm_schema, rope_tables, yarn_mscale,
)

NEG_INF = -2.0e30


# ---------------------------------------------------------------------------
# Schemas
# ---------------------------------------------------------------------------


def attn_schema(cfg: ModelConfig, mesh, cross: bool = False) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    hp = padded_heads(h, mesh) if mesh is not None else h
    s = {
        "wq": ParamDesc((d, hp, hd), ("embed", "heads", "head_dim"), cfg.param_dtype),
        "wk": ParamDesc((d, kv, hd), ("embed", "kv_heads", "head_dim"), cfg.param_dtype),
        "wv": ParamDesc((d, kv, hd), ("embed", "kv_heads", "head_dim"), cfg.param_dtype),
        "wo": ParamDesc((hp, hd, d), ("heads", "head_dim", "embed"), cfg.param_dtype),
    }
    if cfg.qk_norm:
        s["q_norm"] = norm_schema(hd, "rmsnorm", cfg.param_dtype)
        s["k_norm"] = norm_schema(hd, "rmsnorm", cfg.param_dtype)
    return s


def mla_schema(cfg: ModelConfig, mesh) -> Dict:
    mla = cfg.mla
    assert mla is not None
    d, h = cfg.d_model, cfg.num_heads
    hp = padded_heads(h, mesh) if mesh is not None else h
    qk_hd = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    r = mla.kv_lora_rank
    s = {
        "w_dkv": ParamDesc((d, r + mla.qk_rope_head_dim), ("embed", None), cfg.param_dtype),
        "w_uk": ParamDesc((r, hp, mla.qk_nope_head_dim), (None, "heads", "head_dim"), cfg.param_dtype),
        "w_uv": ParamDesc((r, hp, mla.v_head_dim), (None, "heads", "head_dim"), cfg.param_dtype),
        "wo": ParamDesc((hp, mla.v_head_dim, d), ("heads", "head_dim", "embed"), cfg.param_dtype),
        "kv_norm": norm_schema(r, "rmsnorm", cfg.param_dtype),
    }
    if mla.q_lora_rank:
        # low-rank q: x W_qa, RMSNorm, then W_qb up to every head
        qr = mla.q_lora_rank
        s["wq_a"] = ParamDesc((d, qr), ("embed", None), cfg.param_dtype)
        s["q_norm"] = norm_schema(qr, "rmsnorm", cfg.param_dtype)
        s["wq_b"] = ParamDesc((qr, hp, qk_hd), (None, "heads", "head_dim"), cfg.param_dtype)
    else:
        s["wq"] = ParamDesc((d, hp, qk_hd), ("embed", "heads", "head_dim"), cfg.param_dtype)
    return s


def mla_cache_width(mla: MLAConfig) -> int:
    """Values a latent cache row holds: [c_kv, k_pe], zero-padded to a
    multiple of 128. With an unpadded 576-value row the TPU lays the
    stacked cache out sequence-minor, while the decode's in-place row
    write needs it row-minor, so the program would copy the whole stack
    into the other layout and back on every step."""
    return -(-(mla.kv_lora_rank + mla.qk_rope_head_dim) // 128) * 128


def head_mask(num_real: int, num_padded: int, dtype):
    return (jnp.arange(num_padded) < num_real).astype(dtype)


def q_to_kv_map(num_q_real: int, num_q_padded: int, num_kv: int) -> jnp.ndarray:
    """Which kv head each (possibly padded) q head reads."""
    grp = max(num_q_real // max(num_kv, 1), 1)
    m = jnp.minimum(jnp.arange(num_q_padded) // grp, num_kv - 1)
    return m.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention: training / prefill
# ---------------------------------------------------------------------------


def _block_ranges(n_q_blocks: int, n_kv_blocks: int, q_block: int,
                  kv_block: int, causal: bool, window: int):
    """Static (lo, hi) kv-block range per q block."""
    out = []
    for iq in range(n_q_blocks):
        q_lo, q_hi = iq * q_block, (iq + 1) * q_block - 1
        hi = min((q_hi // kv_block), n_kv_blocks - 1) if causal else n_kv_blocks - 1
        lo = 0
        if window:
            lo = max(0, (q_lo - window + 1) // kv_block)
        out.append((lo, hi))
    return out


def blockwise_attention(q, k, v, *, kv_map, causal=True, window=0,
                        q_block=512, kv_block=512, q_offset=0,
                        softmax_scale=None, constrain=None):
    """q: (B,S,HP,hd); k,v: (B,T,KV,hd). Returns (B,S,HP,hd).

    ``kv_map``: (HP,) int map q head -> kv head. ``q_offset``: absolute
    position of q[0] (cross-chunk prefill continuation). ``constrain``:
    optional fn(x, dims) pinning the online-softmax carries to the head
    sharding — fresh zeros carry no sharding and the partitioner otherwise
    keeps the whole (B,H,qb,hd) f32 accumulator data-sharded only.
    """
    b, s_real, hq, hd = q.shape
    t_real = k.shape[1]
    scale = softmax_scale or 1.0 / math.sqrt(hd)
    q_block = min(q_block, s_real)
    kv_block = min(kv_block, t_real)
    # pad to block multiples; padded kv positions are masked out below and
    # padded q rows are sliced away at the end.
    s = -(-s_real // q_block) * q_block
    t = -(-t_real // kv_block) * kv_block
    if s != s_real:
        q = jnp.pad(q, ((0, 0), (0, s - s_real), (0, 0), (0, 0)))
    if t != t_real:
        k = jnp.pad(k, ((0, 0), (0, t - t_real), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t - t_real), (0, 0), (0, 0)))
    nq, nkv = s // q_block, t // kv_block
    ranges = _block_ranges(nq, nkv, q_block, kv_block, causal, window)

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def kv_step(carry, jblk, qi, q_pos):
        m, l, acc = carry
        # slice k/v in-body: no stacked copies, HBM traffic = one block read
        kj = jax.lax.dynamic_slice_in_dim(k, jblk * kv_block, kv_block, 1)
        vj = jax.lax.dynamic_slice_in_dim(v, jblk * kv_block, kv_block, 1)
        kv_pos = jblk * kv_block + jnp.arange(kv_block)
        kj = jnp.take(kj, kv_map, axis=2)          # (B,kvb,HP,hd) expand GQA
        vj = jnp.take(vj, kv_map, axis=2)
        sres = jnp.einsum("bqhd,bthd->bhqt", qi, kj,
                          preferred_element_type=jnp.float32) * scale
        mask = jnp.broadcast_to(kv_pos[None, :] < t_real,
                                (q_block, kv_block))    # mask kv padding
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window:
            mask &= (q_pos[:, None] - kv_pos[None, :]) < window
        sres = jnp.where(mask[None, None], sres, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sres, axis=-1))
        p = jnp.exp(sres - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqt,bthd->bhqd", p.astype(q.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    outs = []
    for iq, (lo, hi) in enumerate(ranges):
        qi = jax.lax.dynamic_slice_in_dim(q, iq * q_block, q_block, axis=1)
        q_pos = q_offset + iq * q_block + jnp.arange(q_block)
        m0 = jnp.full((b, hq, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hq, q_block), jnp.float32)
        a0 = jnp.zeros((b, hq, q_block, v.shape[-1]), jnp.float32)  # v head dim
        if constrain is not None:
            m0 = constrain(m0, ("batch", "heads", None))
            l0 = constrain(l0, ("batch", "heads", None))
            a0 = constrain(a0, ("batch", "heads", None, None))
        (m, l, acc), _ = jax.lax.scan(
            functools.partial(kv_step, qi=qi, q_pos=q_pos),
            (m0, l0, a0), jnp.arange(lo, hi + 1))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(out.swapaxes(1, 2).astype(q.dtype))   # (B,qb,HP,hd)
    return jnp.concatenate(outs, axis=1)[:, :s_real]


def naive_attention(q, k, v, *, kv_map, causal=True, window=0, q_offset=0,
                    softmax_scale=None):
    """Reference O(S^2)-memory attention (oracle for tests; 'naive' impl)."""
    b, s, hq, hd = q.shape
    t = k.shape[1]
    scale = softmax_scale or 1.0 / math.sqrt(hd)
    k = jnp.take(k, kv_map, axis=2)
    v = jnp.take(v, kv_map, axis=2)
    sres = jnp.einsum("bqhd,bthd->bhqt", q, k,
                      preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(s)
    kv_pos = jnp.arange(t)
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= q_pos[:, None] >= kv_pos[None, :]
    if window:
        mask &= (q_pos[:, None] - kv_pos[None, :]) < window
    sres = jnp.where(mask[None, None], sres, NEG_INF)
    p = jax.nn.softmax(sres, axis=-1)
    o = jnp.einsum("bhqt,bthd->bqhd", p.astype(q.dtype), v)
    return o


def decode_attention(q, k_cache, v_cache, pos, *, kv_map, window=0,
                     softmax_scale=None, kv_pos=None, n_real_heads=None):
    """One-token attention against a cache — context-parallel safe.

    q: (B,1,HP,hd); caches: (B,S,KV,hd); pos: (B,) index of the new token
    (cache already contains it at ``pos``). ``kv_pos`` (B,S) gives the
    absolute position held in each cache slot (ring-buffer windows); default
    is the linear layout arange(S). Negative kv_pos marks empty slots.

    The cache is NEVER expanded over q-heads: a jnp.take over the kv-head
    dim makes the partitioner all-gather the seq-sharded cache (measured
    8.3 GB/chip/step on chameleon decode_32k — EXPERIMENTS §Perf). Unpadded
    GQA uses the grouped einsum; padded head counts use an all-(h,kv)-pairs
    einsum + one-hot select (KVx extra MXU work is negligible in the
    memory-bound decode regime, and the cache stays context-parallel).
    """
    b, _, hq, hd = q.shape
    s = k_cache.shape[1]
    kv = k_cache.shape[2]
    scale = softmax_scale or 1.0 / math.sqrt(hd)
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    mask = (kv_pos <= pos[:, None]) & (kv_pos >= 0)
    if window:
        mask &= (pos[:, None] - kv_pos) < window
    grouped = (hq % kv == 0) and (n_real_heads is None or n_real_heads == hq)
    if grouped:
        g = hq // kv
        qg = q.reshape(b, 1, kv, g, hd)
        sres = jnp.einsum("bqkgd,btkd->bkgqt", qg, k_cache,
                          preferred_element_type=jnp.float32) * scale
        sres = jnp.where(mask[:, None, None, None, :], sres, NEG_INF)
        p = jax.nn.softmax(sres, axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(q.dtype), v_cache)
        return o.reshape(b, 1, hq, hd)
    # padded/uneven mapping: all-pairs scores + one-hot head->kv selection
    sel = jax.nn.one_hot(kv_map, kv, dtype=jnp.float32)        # (HP, KV)
    s_all = jnp.einsum("bqhd,btkd->bhkt", q, k_cache,
                       preferred_element_type=jnp.float32) * scale
    sres = jnp.einsum("bhkt,hk->bht", s_all, sel)
    sres = jnp.where(mask[:, None, :], sres, NEG_INF)
    p = jax.nn.softmax(sres, axis=-1)                          # (B,HP,S)
    pv = jnp.einsum("bht,btkd->bhkd", p.astype(q.dtype), v_cache)
    o = jnp.einsum("bhkd,hk->bhd", pv.astype(jnp.float32),
                   sel).astype(q.dtype)
    return o[:, None].reshape(b, 1, hq, hd)


def write_decode_rows(cache, rows, slot, layer=None):
    """Write one row per batch slot: ``cache[b, slot[b]] = rows[b]``.

    ``layer`` None: ``cache`` is one layer's (B,S,...) buffer, rewritten
    with a one-hot masked select, NOT a scatter: scattering at a traced
    per-row index on the model-sharded seq dim makes the partitioner
    all-gather the whole cache every step (measured 8.3 GB/chip on
    chameleon decode_32k — EXPERIMENTS §Perf). The select is elementwise
    and stays context-parallel.

    ``layer`` given: ``cache`` is a segment's stacked (L,B,S,...) buffer
    carried through the decode scan, and the rows are scattered into it at
    ``(layer, b, slot[b])``, in place. Only for a cache whose seq dim is not
    sharded (``models.model.decode_writes_in_place``); where the batch dim
    is sharded, the partitioner gathers the B rows and their indices, not
    the cache.
    """
    rows = rows.astype(cache.dtype)
    if layer is None:
        b, s = cache.shape[:2]
        wmask = (jnp.arange(s)[None, :] == slot[:, None]).reshape(
            (b, s) + (1,) * (cache.ndim - 2))
        return jnp.where(wmask, rows[:, None], cache)
    return cache.at[layer, jnp.arange(cache.shape[1]), slot].set(rows)


# ---------------------------------------------------------------------------
# Full GQA attention block (projections + core + out-proj)
# ---------------------------------------------------------------------------


def decode_attention_cp(q, k_c, v_c, pos, *, kv_map, window, n_real_heads,
                        shd, scale=None):
    """Context-parallel flash-decode: shard_map over the model axis.

    Each model shard holds a contiguous seq chunk of the cache, computes its
    local masked partial softmax (scores never leave VMEM-sized chunks) and
    the shards LSE-combine with three tiny psums — the comm pattern the
    Pallas decode kernel's (o, m, l) outputs feed on real TPUs. This removes
    the full-cache f32 score pipeline the one-shot GSPMD path materializes
    (measured 1.5 TB/chip/step HBM traffic on chameleon decode_32k).
    """
    mesh = shd.mesh
    b, _, hq, hd = q.shape
    s = k_c.shape[1]
    tp = shd.axis_sizes.get("model", 1)
    if mesh is None or tp == 1 or s % tp != 0:
        o = decode_attention(q.astype(k_c.dtype), k_c, v_c, pos,
                             kv_map=kv_map, window=window,
                             n_real_heads=n_real_heads, softmax_scale=scale)
        return o
    chunk = s // tp
    scale = scale or 1.0 / math.sqrt(hd)

    def local(qf, kl, vl, posf):
        idx = jax.lax.axis_index("model")
        off = idx * chunk
        lg = jnp.einsum("bqhd,btkd->bhkt", qf, kl,
                        preferred_element_type=jnp.float32)[:, :, :, :] * scale
        sel = jax.nn.one_hot(kv_map, kl.shape[2], dtype=jnp.float32)
        sres = jnp.einsum("bhkt,hk->bht", lg, sel)
        t_pos = off + jnp.arange(chunk)[None, :]
        mask = t_pos <= posf[:, None]
        if window:
            mask &= (posf[:, None] - t_pos) < window
        sres = jnp.where(mask[:, None, :], sres, NEG_INF)
        m = jnp.max(sres, axis=-1)                          # (B,H)
        pr = jnp.exp(sres - m[..., None])
        l = jnp.sum(pr, axis=-1)
        pv = jnp.einsum("bht,btkd->bhkd", pr.astype(qf.dtype), vl)
        o = jnp.einsum("bhkd,hk->bhd", pv.astype(jnp.float32), sel)
        m_all = jax.lax.pmax(m, "model")
        w = jnp.exp(m - m_all) * l
        wsum = jax.lax.psum(w, "model")
        o = jax.lax.psum(o * jnp.exp(m - m_all)[..., None], "model")
        return (o / jnp.maximum(wsum, 1e-30)[..., None]).astype(qf.dtype)

    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    o = shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, "model"), P(None, "model"), P()),
        out_specs=P(), axis_names={"model"}, check_vma=False,
    )(q, k_c, v_c, pos)
    return o[:, None] if o.ndim == 3 else o


def decode_kernel_applies(cfg: ModelConfig, shd: ShardingCtx, window: int,
                          seq: int) -> bool:
    """Whether a GQA self-attention decode against a segment's stacked
    cache, written in place (``cache_layer`` given), attends with the
    flash-decode kernel where the program lowers for a TPU: a full
    (unwindowed) cache of whole kernel blocks, query heads that group over
    the KV heads, and a program on one device (the kernel is not
    partitioned, so a sharded batch would gather the cache)."""
    return (not window and cfg.num_heads % cfg.num_kv_heads == 0
            and (shd.mesh is None or shd.mesh.size == 1)
            and seq % flash_decode.kv_block_for(seq) == 0)


def _decode_from_stack(q, k_c, v_c, layer, pos, *, kv_map, n_real_heads):
    """Decode attention of layer ``layer`` of a stacked (L,B,S,KV,hd)
    cache: the flash-decode kernel where the program lowers for a TPU,
    which reads each slot only up to its position; elsewhere the layer
    sliced out of the stack and ``decode_attention`` over all of it."""
    def kernel(q, k_c, v_c, layer, pos):
        return flash_decode.decode_attention(q[:, 0], k_c, v_c, layer,
                                             pos)[:, None]

    def sliced(q, k_c, v_c, layer, pos):
        return decode_attention(q, k_c[layer].astype(q.dtype),
                                v_c[layer].astype(q.dtype), pos,
                                kv_map=kv_map, n_real_heads=n_real_heads)
    return jax.lax.platform_dependent(q, k_c, v_c, layer, pos, tpu=kernel,
                                      default=sliced)


def gqa_attention(p, x, cfg: ModelConfig, shd: ShardingCtx, rcfg, **kw):
    """Unified GQA attention, under the name scope ``attn``.

    Training/prefill: ``positions`` is (S,) or (B,S); returns (out[, cache]).
    Decode: pass ``cache`` + ``decode_pos`` (B,); x is (B,1,D). With
    ``cache_layer``, ``cache`` holds the segment's stacked (L,B,S,KV,hd)
    k/v and this layer is index ``cache_layer``: the new rows are written
    into the stack in place, attention reads the layer from it
    (``_decode_from_stack`` where ``decode_kernel_applies``), and the
    returned cache is the stack.
    Cross-attention: ``kv_x`` is the encoder output (prefill/train);
    ``cross_decode`` reads the cached encoder k/v without updating.
    """
    with jax.named_scope("attn"):
        return _gqa(p, x, cfg, shd, rcfg, **kw)


def _gqa(p, x, cfg: ModelConfig, shd: ShardingCtx, rcfg, *, positions,
         kv_x=None, causal=True, window=0, cache: Optional[Dict] = None,
         decode_pos=None, cache_layer=None, return_cache=False,
         cross_decode=False):
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    hp = p["wq"].shape[1]
    kv_map = q_to_kv_map(h, hp, kv)
    mask = head_mask(h, hp, x.dtype)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
    use_rope = cfg.rope_theta > 0 and kv_x is None and not cross_decode

    if cross_decode:
        # cross-attention decode: cache holds encoder k/v; nothing to update
        k_c, v_c = cache["k"].astype(x.dtype), cache["v"].astype(x.dtype)
        o = decode_attention(q, k_c, v_c,
                             jnp.full((x.shape[0],), k_c.shape[1] - 1,
                                      jnp.int32),
                             kv_map=kv_map, n_real_heads=h)
        o = o * mask[None, None, :, None]
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        return (out, cache) if return_cache else out

    src = kv_x if kv_x is not None else x
    knew = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    vnew = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if cfg.qk_norm:
        knew = apply_norm(p["k_norm"], knew, "rmsnorm", cfg.norm_eps)

    if cache is None or decode_pos is None:
        # ---- training / prefill / encoder ----
        if use_rope:
            cos, sin = rope_tables(positions, hd, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            knew = apply_rope(knew, cos, sin)
        # rope's rotate-half concat loses the head sharding; without this
        # constraint the partitioner replicates attention internals over the
        # model axis (measured: +25 GB/chip on nemotron train_4k)
        q = shd.constrain(q, ("batch", None, "heads", None))
        knew = shd.constrain(knew, ("batch", None, "kv_heads", None))
        o = blockwise_attention(
            q, knew, vnew, kv_map=kv_map, causal=causal, window=window,
            q_block=rcfg.attn_q_block, kv_block=rcfg.attn_kv_block,
            constrain=shd.constrain if shd.mesh is not None else None) \
            if rcfg.attention_impl != "naive" else \
            naive_attention(q, knew, vnew, kv_map=kv_map, causal=causal,
                            window=window)
        o = o * mask[None, None, :, None]
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        if return_cache:
            return out, {"k": knew, "v": vnew}
        return out

    # ---- self-attention decode ----
    b = x.shape[0]
    if use_rope:
        cos, sin = rope_tables(decode_pos[:, None], hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        knew = apply_rope(knew, cos, sin)
    n_slots = cache["k"].shape[-3]
    ring = bool(window) and n_slots <= window       # ring-buffer window cache
    if ring:
        slot = decode_pos % n_slots
        # absolute position held in each slot after the write
        j = jnp.arange(n_slots)[None, :]
        kv_pos = decode_pos[:, None] - ((decode_pos[:, None] - j) % n_slots)
    else:
        slot = decode_pos
        kv_pos = None
    new_cache = {name: write_decode_rows(cache[name], rows[:, 0], slot,
                                         cache_layer)
                 for name, rows in (("k", knew), ("v", vnew))}
    k_c, v_c = new_cache["k"], new_cache["v"]
    if cache_layer is not None and decode_kernel_applies(cfg, shd, window,
                                                         n_slots):
        o = _decode_from_stack(q.astype(x.dtype), k_c, v_c, cache_layer,
                               decode_pos, kv_map=kv_map, n_real_heads=h)
    else:
        if cache_layer is not None:
            k_c, v_c = k_c[cache_layer], v_c[cache_layer]
        k_c, v_c = k_c.astype(x.dtype), v_c.astype(x.dtype)
        if not ring:
            # linear cache: context-parallel flash-decode over the model axis
            o = decode_attention_cp(q, k_c, v_c, decode_pos, kv_map=kv_map,
                                    window=window, n_real_heads=h, shd=shd)
        else:
            o = decode_attention(q, k_c, v_c, decode_pos, kv_map=kv_map,
                                 window=window, kv_pos=kv_pos,
                                 n_real_heads=h)
    o = o * mask[None, None, :, None]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): latent KV cache + absorbed-weight decode
# ---------------------------------------------------------------------------


def _latent_rows(c_kv, k_pe, mla: MLAConfig):
    """[c_kv, k_pe] along the last axis, zero-padded to the cache row."""
    pad = mla_cache_width(mla) - c_kv.shape[-1] - k_pe.shape[-1]
    parts = [c_kv, k_pe.astype(c_kv.dtype)]
    if pad:
        parts.append(jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype))
    return jnp.concatenate(parts, -1)


def mla_attention(p, x, cfg: ModelConfig, shd: ShardingCtx, rcfg, *,
                  positions, cache=None, decode_pos=None, cache_layer=None,
                  return_cache=False):
    """DeepSeek-V2 latent attention (arXiv:2405.04434 §2.1).

    q from x (full rank, or low rank: RMSNorm(x W_qa) W_qb); a shared
    latent ``c_kv = RMSNorm(x W_dkv[:r])`` with one rope key ``k_pe`` for
    all heads; rope (YaRN where configured) on the last ``rope`` channels
    of q and on k_pe (rotate-half); the softmax scaled by
    mscale^2 / sqrt(nope + rope). The cache row per token is
    ``[c_kv, rotated k_pe]``, zero-padded (``mla_cache_width``). Decode
    scores in the absorbed form:
    q_nope W_uk against c_kv, W_uv after the weighted sum. With
    ``cache_layer``, ``cache["lat"]`` is the segment's stacked (L,B,S,R)
    latent and this layer's new rows are written into it in place.
    """
    with jax.named_scope("mla"):
        return _mla(p, x, cfg, shd, rcfg, positions=positions, cache=cache,
                    decode_pos=decode_pos, cache_layer=cache_layer,
                    return_cache=return_cache)


def _mla(p, x, cfg: ModelConfig, shd: ShardingCtx, rcfg, *, positions,
         cache, decode_pos, cache_layer, return_cache):
    mla = cfg.mla
    h = cfg.num_heads
    hp = p["wo"].shape[0]
    mask = head_mask(h, hp, x.dtype)
    nope, rope_d, r = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.kv_lora_rank
    mscale = yarn_mscale(mla.rope_factor, mla.rope_mscale_all_dim)
    scale = mscale * mscale / math.sqrt(nope + rope_d)

    if mla.q_lora_rank:
        c_q = apply_norm(p["q_norm"], jnp.einsum("bsd,dr->bsr", x, p["wq_a"]),
                         "rmsnorm", cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", c_q, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    dkv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv_new, k_pe_new = dkv[..., :r], dkv[..., r:]
    c_kv_new = apply_norm(p["kv_norm"], c_kv_new, "rmsnorm", cfg.norm_eps)

    if cache is None or decode_pos is None:
        # ---- train / prefill: explicit k, v ----
        cos, sin = rope_tables(positions, rope_d, cfg.rope_theta, mla)
        q_rope = apply_rope(q_rope, cos, sin)
        k_pe = apply_rope(k_pe_new[:, :, None, :], cos, sin)   # (B,S,1,rope)
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv_new, p["w_uk"])
        v = jnp.einsum("bsr,rhk->bshk", c_kv_new, p["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + (rope_d,))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        # concat of head-sharded k_nope with head-replicated k_pe loses the
        # head sharding — without these constraints the partitioner
        # replicates q/k/v over the model axis (measured: +57 GB/chip).
        bhd = ("batch", None, "heads", None)
        k = shd.constrain(k, bhd)
        qq = shd.constrain(qq, bhd)
        v = shd.constrain(v, bhd)
        kv_map = jnp.arange(hp, dtype=jnp.int32)
        o = blockwise_attention(
            qq, k, v, kv_map=kv_map, causal=True,
            q_block=rcfg.attn_q_block, kv_block=rcfg.attn_kv_block,
            softmax_scale=scale,
            constrain=shd.constrain if shd.mesh is not None else None)
        o = o * mask[None, None, :, None]
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        if return_cache:
            return out, {"lat": _latent_rows(c_kv_new, k_pe[:, :, 0, :], mla)}
        return out

    # ---- decode: absorbed form against the latent cache ----
    cos, sin = rope_tables(decode_pos[:, None], rope_d, cfg.rope_theta, mla)
    q_rope = apply_rope(q_rope, cos, sin)
    k_pe = apply_rope(k_pe_new[:, :, None, :], cos, sin)[:, :, 0, :]
    new_lat = _latent_rows(c_kv_new[:, 0], k_pe[:, 0], mla)
    lat_all = write_decode_rows(cache["lat"], new_lat, decode_pos, cache_layer)
    lat = lat_all if cache_layer is None else lat_all[cache_layer]
    latx = lat.astype(x.dtype)
    # scores: q_nope absorbed through w_uk beside the decoupled rope
    # channels, against whole cache rows [c_kv, k_pe] in one product (no
    # slice of the cache is copied out); the weighted sum likewise, of
    # which the c_kv part goes on through w_uv
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["w_uk"])
    q_cat = _latent_rows(q_lat, q_rope.astype(q_lat.dtype), mla)
    sres = jnp.einsum("bqhr,btr->bhqt", q_cat, latx,
                      preferred_element_type=jnp.float32) * scale
    t_pos = jnp.arange(lat.shape[1])[None, :]
    valid = t_pos <= decode_pos[:, None]
    sres = jnp.where(valid[:, None, None, :], sres, NEG_INF)
    pr = jax.nn.softmax(sres, axis=-1)
    o_lat = jnp.einsum("bhqt,btr->bqhr", pr.astype(x.dtype), latx)[..., :r]
    o = jnp.einsum("bqhr,rhk->bqhk", o_lat, p["w_uv"])
    o = o * mask[None, None, :, None]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"lat": lat_all}
