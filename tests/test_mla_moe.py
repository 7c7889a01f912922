"""DeepSeek-V2's mechanisms in the program: YaRN rope, group-limited
greedy routing, an expert layer that holds a share of the router's
experts and drops nothing, the latent cache written in place in both of
the model's segments, and the engine's routed counters."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config, get_smoke_config
from repro.configs.base import MoEConfig
from repro.distribution.sharding import ShardingCtx, init_params
from repro.models import build_params, build_schedule, model as model_mod
from repro.models.layers import rope_tables, yarn_inv_freq, yarn_mscale
from repro.models.moe import _capacity, apply_moe, moe_schema, route_topk


def test_yarn_table_follows_the_published_formulas():
    """DeepSeek-V2's rope (dim 64, base 1e4, factor 40 over 4096): the
    correction range is channels 10..23; below it the original frequency,
    above it the frequency over 40, a linear blend between."""
    mla = get_config("deepseek-v2-236b").mla
    inv = yarn_inv_freq(64, 10000.0, mla)
    extra = 10000.0 ** (-np.arange(32) * 2 / 64)
    inter = extra / 40

    def corr(b):
        return 64 * math.log(4096 / (b * 2 * math.pi)) / (2 * math.log(1e4))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    keep = 1 - np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, inter * (1 - keep) + extra * keep,
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], inter[23:], rtol=1e-6)
    # cos/sin keep their scale (mscale == mscale_all_dim); the softmax's
    # scale gains mscale^2
    cos, sin = rope_tables(jnp.array([5]), 64, 10000.0, mla)
    np.testing.assert_allclose(np.asarray(cos[0]), np.cos(5 * inv),
                               rtol=1e-5, atol=1e-6)
    m = yarn_mscale(40.0, 0.707)
    assert abs(m - 1.26080) < 1e-5
    assert abs(m * m / math.sqrt(192) - 0.114721) < 1e-6


def _route_cfg(**kw):
    base = dict(num_experts=8, top_k=2, expert_ff=8, router_experts=8)
    base.update(kw)
    return MoEConfig(**base)


def test_group_limited_routing_differs_from_plain_top_k():
    """8 experts in 4 groups of 2, top-2. Scores: group 0 holds 0.30 and
    0.01, group 1 holds 0.20 and 0.19. Plain top-2 takes experts 0 and 2;
    keeping the one best group takes 0 and 1, with the scores themselves
    as weights (not renormalised), times the routed scale."""
    p = np.full(8, 0.3 / 6)
    p[:4] = [0.30, 0.01, 0.20, 0.19]
    p /= p.sum()
    x = jnp.eye(8)[:1]                              # x W_r = log p
    w_r = jnp.zeros((8, 8)).at[0].set(jnp.log(jnp.asarray(p)))
    plain, e_plain, _ = route_topk(w_r, x, _route_cfg())
    grouped, e_grp, _ = route_topk(w_r, x, _route_cfg(
        expert_groups=4, top_k_groups=1, renormalize_top_k=False,
        routed_scale=16.0))
    assert sorted(np.asarray(e_plain[0]).tolist()) == [0, 2]
    assert sorted(np.asarray(e_grp[0]).tolist()) == [0, 1]
    np.testing.assert_allclose(np.asarray(grouped[0]), 16 * p[[0, 1]],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(plain[0]).sum(), 1.0, rtol=1e-5)


def _share_cfg(held=4, first=0, router=16, dtype="float32"):
    cfg = get_smoke_config("deepseek-v2-236b")
    return dataclasses.replace(
        cfg, param_dtype=dtype, dtype=dtype,
        moe=dataclasses.replace(cfg.moe, num_experts=held,
                                router_experts=router, first_expert=first,
                                expert_groups=4, top_k_groups=2, top_k=3))


def _ffn(x, w_gate, w_in, w_out):
    g = x @ w_gate
    return ((g / (1 + np.exp(-g))) * (x @ w_in)) @ w_out


def test_a_held_share_drops_no_token_where_capacity_would(mesh1):
    """Every token routed to held experts 0 and 1 (of 16 in the router):
    a capacity of ceil(T k / E x 1.25) would drop most of them; the held
    share computes each token's part exactly, by a per-token loop."""
    cfg = _share_cfg()
    m = cfg.moe
    p = init_params(moe_schema(cfg, mesh1), jax.random.PRNGKey(0))
    T = 64
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model))
    # the router sees one large positive feature: experts 0 and 1 win
    x = x.at[..., 0].set(6.0)
    router = jnp.zeros_like(p["router"]).at[0, :2].set(3.0)
    p = dict(p, router=router)
    assert _capacity(T, dataclasses.replace(m, num_experts=16)) < T
    y, aux = apply_moe(p, x, cfg, ShardingCtx(mesh1), RunConfig())
    gate, eidx, _ = route_topk(router, x[0], m)
    xf = np.asarray(x[0], np.float64)
    expect = np.zeros_like(xf)
    for t in range(T):
        for j in range(m.top_k):
            e = int(eidx[t, j])
            if e < m.num_experts:
                expect[t] += float(gate[t, j]) * _ffn(
                    xf[t], *(np.asarray(p[k][e], np.float64)
                             for k in ("w_gate", "w_in", "w_out")))
    sh = p["shared"]
    expect += _ffn(xf, *(np.asarray(sh[k], np.float64)
                         for k in ("w_gate", "w_in", "w_out")))
    assert set(np.asarray(eidx[:, :2]).ravel().tolist()) == {0, 1}
    np.testing.assert_allclose(np.asarray(y[0]), expect, rtol=2e-4,
                               atol=2e-4)
    assert int(aux["moe_assignments"]) == T * m.top_k
    assert int(aux["moe_assignments_held"]) == int(
        np.sum(np.asarray(eidx) < m.num_experts))
    assert int(aux["moe_experts_touched"]) == len(
        {int(e) for e in np.asarray(eidx).ravel() if e < m.num_experts})


def test_decode_writes_the_latent_cache_in_place_in_both_segments(
        mesh1, rcfg_small, monkeypatch):
    """The dense layer (an unrolled segment of one) and the scanned expert
    layers both write their new latent rows into the stacked cache in
    place; logits and caches are bit-identical to the masked select's,
    over two steps, and exactly one row per slot and layer changed."""
    cfg = _share_cfg(dtype="bfloat16")
    shd = ShardingCtx(mesh1)
    params = build_params(cfg, mesh1, jax.random.PRNGKey(0))
    from repro.models import cache_schema
    caches = init_params(cache_schema(cfg, 3, 32), jax.random.PRNGKey(1))
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 8))
    caches = jax.tree.map(lambda a: jax.random.normal(
        next(keys), a.shape).astype(a.dtype), caches)
    segs = build_schedule(cfg)
    assert [s.kind for s in segs] == ["dense_prefix", "moe"]
    assert [model_mod.decode_writes_in_place(s, c, shd, rcfg_small)
            for s, c in zip(segs, caches)] == [True, True]
    tokens = jnp.array([[1], [2], [3]], jnp.int32)
    pos = jnp.array([0, 15, 31], jnp.int32)

    def two_steps():
        step = jax.jit(lambda p, c, t, q: model_mod.forward_decode(
            p, c, t, q, cfg, shd, rcfg_small))
        out, c, t = [], caches, tokens
        for q in (pos, jnp.minimum(pos + 1, 31)):
            logits, c = step(params, c, t, q)
            out.append((logits, c))
            t = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return out
    in_place = two_steps()
    monkeypatch.setattr(model_mod, "decode_writes_in_place",
                        lambda *a: False)
    masked = two_steps()
    for (la, ca), (lb, cb) in zip(in_place, masked):
        np.testing.assert_array_equal(np.asarray(la, np.float32),
                                      np.asarray(lb, np.float32))
        for a, b in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    for seg in range(2):
        first = np.asarray(in_place[0][1][seg]["lat"], np.float32)
        before = np.asarray(caches[seg]["lat"], np.float32)
        changed = np.any(first != before, axis=3)
        expect = np.zeros_like(changed)
        expect[:, np.arange(3), np.asarray(pos)] = True
        np.testing.assert_array_equal(changed, expect)


@pytest.mark.parametrize("held_share", [True, False])
def test_engine_counts_routed_work_on_the_device(held_share, rcfg_small):
    """A share-holding model's engine totals its programs' routed counts
    (read only by counters()) and reads 2 in-place segments; a model whose
    layers hold every expert exports no routed counters."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import Request
    cfg = _share_cfg(dtype="bfloat16") if held_share else \
        get_smoke_config("deepseek-v2-236b")
    eng = ServeEngine(cfg, rcfg_small, make_host_mesh(1, 1), batch_slots=2,
                      max_seq=32)
    before = eng.counters()
    assert before["nk_decode_cache_inplace_segments"] == 2.0
    for i, n in enumerate((5, 9)):
        eng.submit(Request(tenant_id=0, prompt=list(range(1, n + 1)),
                           max_new_tokens=4, req_id=i))
    eng.run_until_drained()
    after = eng.counters()
    if not held_share:
        assert not any(k.startswith("nk_moe") for k in after)
        return
    layers = cfg.num_layers - cfg.dense_layer_prefix
    # prompt tokens once each, then one token a slot a decode step
    tokens = 5 + 9 + 2 * eng.decode_steps
    assert after["nk_moe_experts_held"] == 4.0
    assert after["nk_moe_assignments_total"] == \
        tokens * cfg.moe.top_k * layers
    assert 0 < after["nk_moe_assignments_held_total"] < \
        after["nk_moe_assignments_total"]
    assert 0 < after["nk_moe_experts_touched_total"] <= \
        (2 + eng.decode_steps) * layers * 4
    assert eng.counters() == after
