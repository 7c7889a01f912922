"""Mixture-of-Experts: top-k routing with sort-based capacity dispatch (EP).

Dispatch is the sort/gather formulation (no (T,E,C) one-hot tensor is ever
materialized, which would be infeasible at prefill_32k scale):

  1. top-k per token, flatten (T*k) assignments,
  2. stable-sort by expert; position-in-expert via cumulative counts,
  3. drop overflow beyond capacity C = ceil(T*k/E * cf),
  4. gather to (E, C, D) — experts sharded over the model axis (EP), so
     this gather IS the dispatch communication (XLA lowers it to the
     all-to-all / gather pattern the roofline's collective term reports),
  5. batched expert GEMMs, weighted scatter-add back.

A layer told that it holds a share of the router's experts (expert
parallelism, ``MoEConfig.holds_share``: experts ``first_expert`` ..
``first_expert + num_experts - 1`` of ``router_experts``) routes over all
of them and computes only its own experts' part, with no capacity and so
no drops; the exchange with the chips that hold the rest is not part of
this layer.

Supports DeepSeek-V2 shared experts (always-on dense branch of size
num_shared*shared_ff), its group-limited greedy routing, and Arctic's
parallel dense-residual branch. Aux losses: switch load-balance + router
z-loss. Stable name scopes: ``moe``, ``moe/route``, ``moe/experts``,
``moe/shared``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distribution.sharding import ParamDesc, ShardingCtx
from repro.models.layers import apply_mlp, f32, mlp_schema


def moe_schema(cfg: ModelConfig, mesh) -> Dict:
    m = cfg.moe
    d = cfg.d_model
    pd = cfg.param_dtype
    glu = cfg.activation == "silu_glu"
    s: Dict = {
        "router": ParamDesc((d, m.router_experts), ("embed", "experts"),
                            "float32"),
        "w_in": ParamDesc((m.num_experts, d, m.expert_ff),
                          ("experts", "embed", None), pd),
        "w_out": ParamDesc((m.num_experts, m.expert_ff, d),
                           ("experts", None, "embed"), pd),
    }
    if glu:
        s["w_gate"] = ParamDesc((m.num_experts, d, m.expert_ff),
                                ("experts", "embed", None), pd)
    if m.num_shared_experts:
        ff = m.num_shared_experts * (m.shared_ff or m.expert_ff)
        s["shared"] = mlp_schema(d, ff, cfg.activation, pd)
    if m.parallel_dense:
        s["dense"] = mlp_schema(d, cfg.d_ff, cfg.activation, pd)
    return s


def _capacity(tokens: int, m) -> int:
    c = int(tokens * m.top_k * m.capacity_factor / m.num_experts) + 1
    return max(8, min(c, tokens)) if tokens >= 8 else max(1, min(c, tokens))


def route_topk(router_w, x_flat, m) -> Tuple[jax.Array, jax.Array, Dict]:
    """Returns (gate_weights (T,k), expert_idx (T,k), aux metrics).

    Softmax scores over the router's ``router_experts`` outputs (f32).
    With ``expert_groups`` > 1, group-limited greedy (DeepSeek-V2): each
    group scores its best expert, the ``top_k_groups`` best groups are
    kept and every other group's scores are zeroed before the top-k.
    The top-k weights are the scores, divided by their sum where
    ``renormalize_top_k``, times ``routed_scale``."""
    logits = jnp.einsum("td,de->te", f32(x_flat), f32(router_w))
    probs = jax.nn.softmax(logits, axis=-1)
    T, E = probs.shape
    scores = probs
    if m.expert_groups > 1:
        grouped = probs.reshape(T, m.expert_groups, E // m.expert_groups)
        _, keep = jax.lax.top_k(jnp.max(grouped, -1), m.top_k_groups)
        kept = jnp.any(jax.nn.one_hot(keep, m.expert_groups,
                                      dtype=jnp.bool_), axis=1)
        scores = jnp.where(kept[..., None], grouped, 0.0).reshape(T, E)
    gate, eidx = jax.lax.top_k(scores, m.top_k)
    if m.renormalize_top_k:
        gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    if m.routed_scale != 1.0:
        gate = gate * m.routed_scale
    # switch-style load-balance loss + router z-loss
    frac = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (T * m.top_k)
    imp = jnp.mean(probs, axis=0)
    lb_loss = E * jnp.sum(frac * imp)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
           "moe_max_frac": jnp.max(frac)}
    return gate, eidx, aux


def _dispatch_tables(eidx_g, gate_g, E: int, C: int, T_g: int, k: int):
    """Per-group dispatch: token/weight tables (E*C,) + inverse slots (T_g*k,).

    Sort-based: stable-sort assignments by expert, position-in-expert via
    cumulative counts, truncate at capacity. All shapes are group-local.
    """
    e_flat = eidx_g.reshape(-1)                                 # (T_g*k,)
    tok_flat = jnp.arange(T_g * k, dtype=jnp.int32) // k
    w_flat = gate_g.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T_g * k, dtype=jnp.int32) - starts[e_sorted]
    keep = pos_in_e < C
    slot_sorted = jnp.where(keep, e_sorted * C + pos_in_e, E * C)
    table = jnp.full((E * C + 1,), T_g, jnp.int32).at[slot_sorted].set(
        jnp.where(keep, tok_flat[order], T_g))[:-1]             # (E*C,)
    # inverse map: assignment j -> its slot (E*C = dropped)
    inv = jnp.argsort(order, stable=True)                       # j -> sorted pos
    slot_of = slot_sorted[inv]                                  # (T_g*k,)
    drop = jnp.sum(1.0 - keep.astype(jnp.float32)) / (T_g * k)
    return table, slot_of, w_flat, drop


def apply_moe(p, x, cfg: ModelConfig, shd: ShardingCtx, rcfg) -> Tuple[jax.Array, Dict]:
    """x: (B, S, D) -> (y, aux).

    A layer that holds a share of the router's experts
    (``MoEConfig.holds_share``) computes that share's part, dropless
    (``_apply_held``). Otherwise GShard-style grouped dispatch: tokens are
    split into G groups (G = data axis size), each group routes/sorts/
    truncates locally, so every intermediate carries a leading group dim
    sharded over 'data' and an expert dim sharded over 'model' — nothing
    is ever replicated. Capacity is enforced per group (standard practice).
    """
    with jax.named_scope("moe"):
        if cfg.moe.holds_share:
            return _apply_held(p, x, cfg, shd)
        return _apply_capacity(p, x, cfg, shd)


def _apply_held(p, x, cfg: ModelConfig, shd: ShardingCtx):
    """The part of the layer's result that its held experts give, for
    every token, with no capacity: routing is over all
    ``router_experts``; an assignment to expert ``first_expert + j``
    (j < ``num_experts``) is computed here with its weight, any other is
    left to the chip that holds that expert. Every held expert runs on
    every token and a token's output sums them with its weights (zero
    where not routed), so no batch can drop a token. The shared experts
    are added whole. ``aux`` carries int32 counts, summed over layers by
    the segment runner: ``moe_assignments`` (T x top_k),
    ``moe_assignments_held`` (those that land on a held expert) and
    ``moe_experts_touched`` (held experts with at least one)."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    with jax.named_scope("route"):
        gate, eidx, _ = route_topk(p["router"], xf, m)
        local = eidx - m.first_expert                             # (T,k)
        held = (local >= 0) & (local < m.num_experts)
        onehot = jax.nn.one_hot(jnp.where(held, local, -1), m.num_experts,
                                dtype=jnp.float32)                # (T,k,E)
        w = jnp.einsum("tk,tke->te", gate, onehot)                # (T,E)
        touched = jnp.any(jnp.sum(onehot, 1) > 0, axis=0)
        aux = {"moe_assignments": jnp.asarray(eidx.size, jnp.int32),
               "moe_assignments_held": jnp.sum(held, dtype=jnp.int32),
               "moe_experts_touched": jnp.sum(touched, dtype=jnp.int32)}
    with jax.named_scope("experts"):
        h = jnp.einsum("td,edf->etf", xf, p["w_in"])
        if cfg.activation == "silu_glu":
            g = jnp.einsum("td,edf->etf", xf, p["w_gate"])
            h = jax.nn.silu(f32(g)) * f32(h)
        elif cfg.activation == "relu2":
            h = jnp.square(jax.nn.relu(f32(h)))
        else:
            h = jax.nn.gelu(f32(h))
        # the routing weight folds into the hidden activations, so the
        # sum over experts is the contraction of the down projection
        h = (h * w.T[..., None]).astype(x.dtype)
        y = jnp.einsum("etf,efd->td", h, p["w_out"],
                       preferred_element_type=jnp.float32)
        y = y.astype(x.dtype).reshape(b, s, d)
    y = _add_dense_branches(p, x, y, cfg, shd)
    return y, aux


def _add_dense_branches(p, x, y, cfg: ModelConfig, shd: ShardingCtx):
    m = cfg.moe
    if m.num_shared_experts:
        with jax.named_scope("shared"):
            y = y + apply_mlp(p["shared"], x, cfg.activation, shd)
    if m.parallel_dense:
        y = y + apply_mlp(p["dense"], x, cfg.activation, shd)
    return y


def _apply_capacity(p, x, cfg: ModelConfig, shd: ShardingCtx):
    m = cfg.moe
    b, s, d = x.shape
    T = b * s
    G = max(shd.axis_sizes.get("data", 1), 1) if shd.mesh is not None else 1
    while T % G:
        G //= 2
    T_g = T // G
    k, E = m.top_k, m.num_experts
    C = _capacity(T_g, m)

    xf = x.reshape(T, d)
    with jax.named_scope("route"):
        gate, eidx, aux = route_topk(p["router"], xf, m)
    xg = xf.reshape(G, T_g, d)
    gate_g = gate.reshape(G, T_g, k)
    eidx_g = eidx.reshape(G, T_g, k)

    table, slot_of, w_flat, drop = jax.vmap(
        lambda e, w: _dispatch_tables(e, w, E, C, T_g, k))(eidx_g, gate_g)
    # NOTE: dropped slots use clamped indices + masks, never a padding row —
    # a +1 row on a sharded dim makes it unshardable and the partitioner
    # would replicate the whole (G, E*C, d) dispatch buffer on every chip.
    egc = ("expert_group", "experts", None, None)
    xe = jnp.take_along_axis(xg, jnp.minimum(table, T_g - 1)[..., None],
                             axis=1)                            # (G, E*C, d)
    xe = xe * (table < T_g)[..., None].astype(xe.dtype)
    xe = shd.constrain(xe.reshape(G, E, C, d), egc)

    h = jnp.einsum("gecd,edf->gecf", xe, p["w_in"])
    if cfg.activation == "silu_glu":
        g = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])
        h = jax.nn.silu(f32(g)).astype(x.dtype) * h
    elif cfg.activation == "relu2":
        h = jnp.square(jax.nn.relu(f32(h))).astype(x.dtype)
    else:
        h = jax.nn.gelu(f32(h)).astype(x.dtype)
    h = shd.constrain(h, egc)
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_out"])
    ye = shd.constrain(ye, egc)

    # combine: inverse gather (per group), weighted sum over k assignments
    yflat = shd.constrain(ye.reshape(G, E * C, d),
                          ("expert_group", "experts", None))
    picked = jnp.take_along_axis(
        yflat, jnp.minimum(slot_of, E * C - 1)[..., None], axis=1)
    picked = picked * (slot_of < E * C)[..., None].astype(yflat.dtype)
    picked = picked.reshape(G, T_g, k, d)
    y = jnp.sum(f32(picked) * w_flat.reshape(G, T_g, k)[..., None], axis=2)
    y = y.astype(x.dtype).reshape(b, s, d)

    y = _add_dense_branches(p, x, y, cfg, shd)
    aux["moe_drop_frac"] = jnp.mean(drop)
    return y, aux
