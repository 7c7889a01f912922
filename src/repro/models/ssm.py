"""Mamba-2 SSD (state-space duality) block: chunked train path + O(1) decode.

Follows the SSD algorithm of Dao & Gu 2024 (arXiv:2405.21060): quadratic
attention-like compute *within* chunks, linear state recurrence *across*
chunks. The intra-chunk part is the compute hot spot targeted by the Pallas
kernel (repro/kernels/ssd_scan.py); this module is the production JAX path
and the oracle's substrate.

Layout: x (B, L, H, P) heads; B/C (B, L, G, N) in G groups, head h reading
group h // (H / G) (one group: (B, L, N)); dt (B, L, H). State: (B, H, P, N).
Name scopes: ``ssm``, with ``in`` (projections), ``conv``, ``scan``
(prefill) or ``update`` (decode), and ``out`` (gated norm, out-projection).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.distribution.sharding import ParamDesc, ShardingCtx
from repro.models.layers import f32, norm_schema

# the conv's channel streams: x (the inner width), then B and C
STREAMS = ("x", "B", "C")


def _widths(cfg: ModelConfig) -> Dict[str, int]:
    s = cfg.ssm
    gn = s.n_groups * s.state_dim
    return {"x": s.d_inner(cfg.d_model), "B": gn, "C": gn}


def ssm_schema(cfg: ModelConfig, mesh) -> Dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.heads(d)
    w = s.conv_width
    pd = cfg.param_dtype
    out = {
        "w_z": ParamDesc((d, di), ("embed", "ffn"), pd),
        "w_dt": ParamDesc((d, nh), ("embed", "ssm_heads"), pd),
        "w_out": ParamDesc((di, d), ("ffn", "embed"), pd),
        "A_log": ParamDesc((nh,), ("ssm_heads",), "float32", "zeros"),
        "D": ParamDesc((nh,), ("ssm_heads",), "float32", "ones"),
        "dt_bias": ParamDesc((nh,), ("ssm_heads",), "float32", "zeros"),
        "norm": norm_schema(di, "rmsnorm", pd),
    }
    for k, c in _widths(cfg).items():
        dim = "ffn" if k == "x" else None
        out[f"w_{k}"] = ParamDesc((d, c), ("embed", dim), pd)
        out[f"conv_{k}"] = ParamDesc((w, c), ("conv", dim), pd,
                                     "small_normal", 0.5)
        if s.conv_bias:
            out[f"conv_bias_{k}"] = ParamDesc((c,), (dim,), pd, "zeros")
    return out


# ---------------------------------------------------------------------------
# Causal depthwise conv (width W), train + streaming forms
# ---------------------------------------------------------------------------


def causal_conv(u: jax.Array, w: jax.Array) -> jax.Array:
    """u: (B, L, C); w: (W, C) depthwise. Causal: y[t] = sum_j w[j]*u[t-W+1+j]."""
    W = w.shape[0]
    y = u * w[-1]
    for j in range(W - 1):
        shift = W - 1 - j
        y = y + jnp.pad(u, ((0, 0), (shift, 0), (0, 0)))[:, :-shift] * w[j]
    return y


def conv_step(u_t: jax.Array, state: jax.Array, w: jax.Array):
    """u_t: (B, C); state: (B, W-1, C) past inputs. Returns (y_t, state')."""
    full = jnp.concatenate([state, u_t[:, None]], axis=1)    # (B, W, C)
    y = jnp.einsum("bwc,wc->bc", full, w)
    return y, full[:, 1:]


# ---------------------------------------------------------------------------
# SSD core (chunked)
# ---------------------------------------------------------------------------


def _segsum(x: jax.Array) -> jax.Array:
    """x: (..., T). Returns (..., T, T): sum_{k=j+1..i} x[k] on i>=j, -inf above."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    ii = jnp.arange(T)
    mask = ii[:, None] >= ii[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def _grouped(B, C, ndim: int):
    """B and C of one group, without the group axis (``ndim - 1`` dims),
    with a group axis of 1 before the last; grouped ones as they are."""
    if B.ndim == ndim - 1:
        return B[..., None, :], C[..., None, :]
    return B, C


def ssd_chunked(xdt, dA, B, C, chunk: int,
                initial_state: Optional[jax.Array] = None):
    """SSD scan. xdt: (b,l,h,p) = x*dt; dA: (b,l,h) = dt*A (negative);
    B, C: (b,l,n) or, in g groups, (b,l,g,n). Returns (y (b,l,h,p),
    final_state (b,h,p,n))."""
    B, C = _grouped(B, C, 4)
    b, l_real, h, p = xdt.shape
    g, n = B.shape[-2:]
    j = h // g                                    # heads a group
    # pad to a chunk multiple: trailing zeros in xdt and dA=0 (decay exp(0)=1)
    # leave the recurrence and final state untouched; outputs are sliced.
    l = -(-l_real // chunk) * chunk
    if l != l_real:
        pad = ((0, 0), (0, l - l_real))
        xdt = jnp.pad(xdt, pad + ((0, 0), (0, 0)))
        dA = jnp.pad(dA, pad + ((0, 0),))
        B = jnp.pad(B, pad + ((0, 0), (0, 0)))
        C = jnp.pad(C, pad + ((0, 0), (0, 0)))
    nc = l // chunk
    xc = xdt.reshape(b, nc, chunk, g, j, p)
    dAc = dA.reshape(b, nc, chunk, g, j).transpose(0, 3, 4, 1, 2)  # b,g,j,c,Q
    Bc = B.reshape(b, nc, chunk, g, n)
    Cc = C.reshape(b, nc, chunk, g, n)

    A_cum = jnp.cumsum(dAc, axis=-1)                           # (b,g,j,c,Q)
    L = jnp.exp(_segsum(dAc))                                  # (...,c,Q,Q)

    # --- intra-chunk (quadratic, attention-like), C.B once per group ---
    G = jnp.einsum("bclgn,bcsgn->bgcls", Cc, Bc,
                   preferred_element_type=jnp.float32)         # (b,g,c,Q,Q)
    M = jnp.einsum("bgcls,bgjcls->bgjcls", G, L)
    y_diag = jnp.einsum("bgjcls,bcsgjp->bclgjp", M.astype(xdt.dtype), xc,
                        preferred_element_type=jnp.float32)

    # --- chunk states ---
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)            # (b,g,j,c,Q)
    xd = jnp.einsum("bgjcl,bclgjp->bclgjp", decay_states.astype(xdt.dtype),
                    xc)
    states = jnp.einsum("bclgn,bclgjp->bcgjpn", Bc, xd,
                        preferred_element_type=jnp.float32)    # b,c,g,j,p,n

    # --- inter-chunk recurrence (linear scan over chunks) ---
    chunk_decay = jnp.exp(A_cum[..., -1])                      # (b,g,j,c)
    s0 = (jnp.zeros((b, g, j, p, n), jnp.float32) if initial_state is None
          else f32(initial_state).reshape(b, g, j, p, n))

    def step(carry, xs):
        st, dec = xs                                       # (b,g,j,p,n),(b,g,j)
        new = carry * dec[..., None, None] + st
        return new, carry                                  # emit entering state

    sc = states.transpose(1, 0, 2, 3, 4, 5)                    # (c,b,g,j,p,n)
    dc = chunk_decay.transpose(3, 0, 1, 2)                     # (c,b,g,j)
    final_state, entering = jax.lax.scan(step, s0, (sc, dc))
    entering = entering.transpose(1, 0, 2, 3, 4, 5)            # b,c,g,j,p,n

    # --- inter-chunk output ---
    state_decay = jnp.exp(A_cum)                               # (b,g,j,c,Q)
    y_off = jnp.einsum("bclgn,bcgjpn->bclgjp", Cc,
                       entering.astype(xdt.dtype),
                       preferred_element_type=jnp.float32)
    y_off = y_off * state_decay.transpose(0, 3, 4, 1, 2)[..., None]
    y = (y_diag + y_off).reshape(b, l, h, p)[:, :l_real]
    return y, final_state.reshape(b, h, p, n)


def ssd_decode_step(x_t, dt_t, A, B_t, C_t, state):
    """One-token SSD update. x_t: (b,h,p); dt_t: (b,h); A: (h,) negative;
    B_t, C_t: (b,n) or, in g groups, (b,g,n); state: (b,h,p,n). Returns
    (y (b,h,p), state')."""
    B_t, C_t = _grouped(B_t, C_t, 3)
    b, h, p = x_t.shape
    g, n = B_t.shape[-2:]
    # elementwise products, not einsums: the update x B is then one fusion
    # with the decay, and the whole-state outer product is never written
    dA = jnp.exp(f32(dt_t) * A).reshape(b, g, h // g, 1, 1)
    xdt = (f32(x_t) * f32(dt_t)[..., None]).reshape(b, g, h // g, p, 1)
    state = f32(state).reshape(b, g, h // g, p, n) * dA \
        + xdt * f32(B_t)[:, :, None, None, :]
    y = jnp.sum(state * f32(C_t)[:, :, None, None, :], axis=-1)
    return y.reshape(b, h, p).astype(x_t.dtype), state.reshape(b, h, p, n)


# ---------------------------------------------------------------------------
# Full block (projections + conv + SSD + gate + out)
# ---------------------------------------------------------------------------


def _gated_out(p, y, z, s: SSMConfig, eps: float, dtype):
    """RMSNorm over each B/C group's share of the inner width of
    y * silu(z), then the out-projection (the gate comes before the norm,
    as in Mamba-2 and Nemotron-H)."""
    with jax.named_scope("out"):
        gated = y.astype(dtype) * jax.nn.silu(f32(z)).astype(dtype)
        gf = f32(gated).reshape(*gated.shape[:-1], s.n_groups, -1)
        gf = gf * jax.lax.rsqrt(jnp.mean(gf * gf, -1, keepdims=True) + eps)
        normed = (gf.reshape(gated.shape) * f32(p["norm"]["scale"])
                  ).astype(dtype)
        return jnp.einsum("ble,ed->bld", normed, p["w_out"])


def _conv_bias(p, k, y):
    b = p.get(f"conv_bias_{k}")
    return y if b is None else y + b


def ssm_block(p, x, cfg: ModelConfig, shd: ShardingCtx, rcfg, *,
              cache: Optional[Dict] = None, decode: bool = False):
    """x: (B,L,D) (train) or (B,1,D) (decode). Returns (y, cache')."""
    with jax.named_scope("ssm"):
        return _ssm(p, x, cfg, cache=cache, decode=decode)


def _ssm(p, x, cfg: ModelConfig, *, cache, decode):
    s = cfg.ssm
    nh = s.heads(cfg.d_model)
    hp = s.head_dim
    w = s.conv_width
    A = -jnp.exp(f32(p["A_log"]))

    with jax.named_scope("in"):
        z = jnp.einsum("bld,de->ble", x, p["w_z"])
        pre = {k: jnp.einsum("bld,de->ble", x, p[f"w_{k}"]) for k in STREAMS}
        dts = jnp.einsum("bld,dh->blh", x, p["w_dt"])
        dt = jax.nn.softplus(f32(dts) + f32(p["dt_bias"]))

    def groups(u):                    # (..., G*N) -> (..., G, N)
        return u.reshape(*u.shape[:-1], s.n_groups, s.state_dim)

    if not decode:
        with jax.named_scope("conv"):
            cv = {k: jax.nn.silu(f32(_conv_bias(p, k, causal_conv(
                u, p[f"conv_{k}"])))).astype(x.dtype)
                for k, u in pre.items()}
        with jax.named_scope("scan"):
            xh = cv["x"].reshape(*x.shape[:2], nh, hp)
            xdt = (f32(xh) * dt[..., None]).astype(x.dtype)
            y, state = ssd_chunked(xdt, dt * A, groups(cv["B"]),
                                   groups(cv["C"]), s.chunk)
            y = y + f32(xh) * f32(p["D"])[None, None, :, None]
        out = _gated_out(p, y.reshape(*x.shape[:2], -1), z, s, cfg.norm_eps,
                         x.dtype)
        new_cache = None
        if cache is not None:
            # the last w-1 pre-conv inputs of each stream, for the streaming
            # conv to continue from; a prompt shorter than that is preceded
            # by zeros, as the conv itself pads it
            new_cache = {"state": state.astype(cache["state"].dtype)}
            for k, u in pre.items():
                tail = jnp.pad(u, ((0, 0), (w - 1, 0), (0, 0)))[:, -(w - 1):]
                new_cache[f"conv_{k}"] = tail.astype(cache[f"conv_{k}"].dtype)
        return out, new_cache

    # ---- decode ----
    assert cache is not None
    new_cache = {}
    with jax.named_scope("conv"):
        cv = {}
        for k, u in pre.items():
            y_k, new_cache[f"conv_{k}"] = conv_step(
                u[:, 0], cache[f"conv_{k}"].astype(x.dtype), p[f"conv_{k}"])
            cv[k] = jax.nn.silu(f32(_conv_bias(p, k, y_k))).astype(x.dtype)
            new_cache[f"conv_{k}"] = new_cache[f"conv_{k}"].astype(
                cache[f"conv_{k}"].dtype)
    with jax.named_scope("update"):
        xh = cv["x"].reshape(-1, nh, hp)
        y, state = ssd_decode_step(xh, dt[:, 0], A, groups(cv["B"]),
                                   groups(cv["C"]), f32(cache["state"]))
        y = y + xh * f32(p["D"])[None, :, None].astype(x.dtype)
        new_cache["state"] = state.astype(cache["state"].dtype)
    out = _gated_out(p, y.reshape(-1, 1, nh * hp), z, s, cfg.norm_eps,
                     x.dtype)
    return out, new_cache


def ssm_cache_schema(cfg: ModelConfig, batch: int, dtype: str) -> Dict:
    """A layer's recurrent state (f32) and the conv's tails: the last
    ``conv_width - 1`` pre-conv inputs of each stream, in ``dtype``."""
    s = cfg.ssm
    w = s.conv_width
    out = {"state": ParamDesc((batch, s.heads(cfg.d_model), s.head_dim,
                               s.state_dim),
                              ("batch", "ssm_heads", None, None), "float32",
                              "zeros")}
    for k, c in _widths(cfg).items():
        out[f"conv_{k}"] = ParamDesc((batch, w - 1, c),
                                     ("batch", None, "ffn" if k == "x"
                                      else None), dtype, "zeros")
    return out
