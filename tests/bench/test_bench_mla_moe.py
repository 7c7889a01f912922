"""DeepSeek-V2 at one chip's share (bench/configs/deepseek-v2-9l.json):
the program against the plain reference (bench/reference/mla_moe.py) on
seeded random weights at the architecture's smoke widths, the share
against the uncut layer, the file against the program's published
widths, the architecture module against the program's tree, and a smoke
run of the cell."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke
from bench import run, weights
from bench.client import program_config

CELL = "deepseek-v2-9l.decode-heavy"
CONF = json.loads((smoke.ROOT / "bench" / "configs" / "deepseek-v2-9l.json")
                  .read_text())
ARCH = run.module("arch", "mla_moe")
REF = run.module("reference", "mla_moe")

# Program (bf16 weights and activations, f32 accumulation) against the
# float32 reference, largest |logit difference| over every vocabulary
# entry of a prefill and 12 decode steps, logits of standard deviation
# near 1: 0.038-0.068 over seeds 2**31 + 0..5 (the largest where bf16
# rounding moved a token across a top-6 routing boundary); the float8
# control reads 0.35-0.60 on the same sequences. 0.15 leaves twice the
# largest bf16 reading and fails the control by more than two.
LOGIT_TOL = 0.15


def _smoke_model():
    m = dict(CONF["model"])
    m.update(ARCH.SMOKE)
    return m


def _full_logits(w, m, seq, quant=None):
    v = m["vocab_size"]
    probe = jnp.broadcast_to(jnp.arange(v)[:, None], (v, seq.shape[0]))
    return np.asarray(REF.stats(w, m, jnp.asarray(seq), probe,
                                quant=quant)[2]).T          # (S, V)


@pytest.mark.parametrize("seed", [2**31, 2**31 + 3])
def test_prefill_and_decode_through_the_latent_cache_match_the_reference(
        seed):
    """Two sequences prefilled to 20 tokens, then 12 decode steps through
    the latent cache, teacher-forced: every logit within LOGIT_TOL of the
    reference's full forward at that position; the float8 control is
    not."""
    from repro.configs import RunConfig
    from repro.distribution.sharding import ShardingCtx
    from repro.models.model import forward_decode, forward_prefill
    m = _smoke_model()
    cfg = program_config(m)
    w = weights.make_weights(ARCH.layout(m), "bfloat16", seed)
    p = ARCH.program_params(w, m)
    S, P = 32, 20
    toks = np.random.default_rng(seed).integers(
        0, m["vocab_size"], (2, S)).astype(np.int32)
    ref = [_full_logits(w, m, toks[b]) for b in range(2)]
    shd, rcfg = ShardingCtx(None), RunConfig()
    last, caches = forward_prefill(p, jnp.asarray(toks[:, :P]), cfg, shd,
                                   rcfg, max_seq=S)
    got = [(P - 1, np.asarray(last, np.float32))]
    for t in range(P, S):
        lg, caches = forward_decode(p, caches, jnp.asarray(toks[:, t:t + 1]),
                                    jnp.full((2,), t, jnp.int32), cfg, shd,
                                    rcfg)
        got.append((t, np.asarray(lg, np.float32)))
    err = max(np.max(np.abs(g[b] - ref[b][t])) for t, g in got
              for b in range(2))
    assert err < LOGIT_TOL
    f8 = _full_logits(w, m, toks[0], quant="fp8")
    assert np.max(np.abs(f8[P - 1:] - ref[0][P - 1:])) > LOGIT_TOL


def test_the_shares_add_up_to_the_uncut_layer():
    """The router's 64 experts split over 16 shares of 4: the shares'
    routed parts, with the shared experts counted once, equal the
    reference's expert layer holding all 64 (float32 throughout)."""
    from repro.configs import RunConfig
    from repro.distribution.sharding import ShardingCtx
    from repro.models.moe import apply_moe
    m = _smoke_model()
    cfg = dataclasses.replace(program_config(m), param_dtype="float32",
                              dtype="float32")
    mo = m["moe"]
    R, E = mo["router_experts"], mo["num_experts"]
    d, ff = m["d_model"], mo["expert_ff"]
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    full = {"router": jax.random.normal(keys[0], (d, R)) * d ** -0.5,
            "w_gate": jax.random.normal(keys[1], (R, d, ff)) * d ** -0.5,
            "w_in": jax.random.normal(keys[2], (R, d, ff)) * d ** -0.5,
            "w_out": jax.random.normal(keys[3], (R, ff, d)) * ff ** -0.5}
    sff = mo["num_shared_experts"] * mo["shared_ff"]
    shared = {"w_gate": jax.random.normal(keys[4], (d, sff)) * d ** -0.5,
              "w_in": jax.random.normal(keys[5], (d, sff)) * d ** -0.5,
              "w_out": jax.random.normal(keys[6], (sff, d)) * sff ** -0.5}
    x = jax.random.normal(keys[7], (1, 24, d))
    shd, rcfg = ShardingCtx(None), RunConfig()
    total = 0.0
    for first in range(0, R, E):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, first_expert=first))
        p = {"router": full["router"], "shared": shared,
             **{k: full[k][first:first + E]
                for k in ("w_gate", "w_in", "w_out")}}
        y, _ = apply_moe(p, x, c, shd, rcfg)
        total = total + y
    shared_part = REF._mlp(x[0], shared["w_gate"], shared["w_in"],
                           shared["w_out"], None)
    routed = total[0] - (R // E) * shared_part
    uncut = REF._experts(x[0], full, dict(mo, num_experts=R, first_expert=0),
                         None)
    np.testing.assert_allclose(np.asarray(routed + shared_part),
                               np.asarray(uncut + shared_part),
                               rtol=1e-4, atol=1e-4)


def test_the_file_gives_the_published_mla():
    """The program's registered MLAConfig is the file's published block,
    and the reference's YaRN constants are the same numbers."""
    cfg = program_config(CONF["model"])
    pub = CONF["published"]
    for k, v in {**pub["mla"], **pub["yarn"]}.items():
        assert getattr(cfg.mla, k) == v, k
    y = REF.YARN
    assert (y["factor"], y["original_max_position_embeddings"],
            y["beta_fast"], y["beta_slow"], y["mscale"],
            y["mscale_all_dim"]) == tuple(pub["yarn"][k] for k in (
                "rope_factor", "rope_original_max_positions",
                "rope_beta_fast", "rope_beta_slow", "rope_mscale",
                "rope_mscale_all_dim"))
    assert cfg.num_layers == 9 and cfg.d_model == 5120
    assert cfg.num_heads == 128 and cfg.vocab_size == 102400
    assert cfg.norm_eps == 1e-6 and cfg.dense_layer_prefix == 1
    moe = cfg.moe
    assert (moe.num_experts, moe.router_experts, moe.first_expert,
            moe.top_k, moe.expert_groups, moe.top_k_groups,
            moe.routed_scale, moe.renormalize_top_k,
            moe.num_shared_experts) == (10, 160, 0, 6, 8, 3, 16.0, False, 2)
    # the file's catalog keys: the two cuts, and everything else published
    assert CONF["num_hidden_layers"] == 9 and CONF["n_routed_experts"] == 10
    assert {k: (v["published"], v["here"]) for k, v in
            CONF["reduced"].items()} == {"num_hidden_layers": (60, 9),
                                         "n_routed_experts": (160, 10)}


def test_the_yarn_frequencies_agree():
    from repro.models.layers import yarn_inv_freq
    mla = program_config(CONF["model"]).mla
    np.testing.assert_allclose(yarn_inv_freq(64, 1e4, mla),
                               REF.yarn_inv_freq(64, 1e4), rtol=1e-6)


def test_layout_is_the_programs_tree():
    """At smoke widths the arch module's tensors, through program_params,
    have exactly the program's model_schema shapes, and share the drawn
    arrays."""
    from repro.models.model import model_schema
    from repro.distribution.sharding import abstract_params
    m = _smoke_model()
    w = weights.make_weights(ARCH.layout(m), "bfloat16", 2**31 + 5)
    p = ARCH.program_params(w, m)
    want = abstract_params(model_schema(program_config(m), None))
    assert jax.tree.structure(p) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert p["segments"][1]["moe"]["w_in"] is w["moe_layers"]["w_in"]


def test_full_width_counts():
    """At the published widths: 149.2 M attention parameters a layer,
    9.70 GB of weights, a 1152-byte latent row a layer, and a decode step
    that reads about 8.6 GB of weights with 64 tokens."""
    m = CONF["model"]
    assert ARCH.attn_params(m) == 149_225_472
    total = sum(int(np.prod(s)) for _, s, _ in ARCH.layout(m)) * 2
    assert 9.70e9 < total < 9.71e9
    assert ARCH.latent_bytes_per_token(m) == 9 * 1152
    assert 8.3e9 < ARCH.weight_bytes(m, 64) < 8.8e9
    assert ARCH.held_per_token(m) == 6 * 10 / 160
    c = ARCH.moe_call(m, tokens=64 * 8, held_assignments=24 * 8,
                      experts_touched=9 * 8, calls=8)
    assert c["bytes"] > 9 * 8 * ARCH.expert_params(m) * 2


def test_smoke_run_is_correct():
    _, c, _, _, e2e, per = run.load_cell(CELL)
    out = run.run_cell(smoke.args(), c, smoke.smoke_conf("deepseek-v2-9l"),
                       smoke.smoke_mix("decode-heavy"), e2e, per, smoke.PEAK,
                       smoke.DEVICE, lambda msg: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}


def _fault(monkeypatch, fault):
    """Serve with one fault in the program's expert layers; the
    reference (and the weights it reads) stay whole."""
    from bench import client
    if fault == "held_zeroed":
        load = run.module

        def module(kind, name):
            mod = load(kind, name)
            if kind == "arch":
                params = mod.program_params

                def program_params(w, m):
                    p = params(w, m)
                    moe = p["segments"][1]["moe"]
                    p["segments"][1]["moe"] = dict(
                        moe, w_out=jnp.zeros_like(moe["w_out"]))
                    return p
                mod.program_params = program_params
            return mod
        monkeypatch.setattr(run, "module", module)
        return
    config = client.program_config

    def program_config(m):
        cfg = config(m)
        mo = cfg.moe
        change = {"routed_scale_dropped": {"routed_scale": 1.0},
                  "held_range_shifted": {"first_expert": mo.first_expert + 1}}
        return dataclasses.replace(cfg, moe=dataclasses.replace(
            mo, **change[fault]))
    monkeypatch.setattr(client, "program_config", program_config)


def _served_gap(seed):
    """Serve four smoke requests to the end through the cell's serving
    path and return the harness's comparison (``bench/check.py`` ``gaps``)
    of every token served."""
    from bench import check, traffic
    from bench.client import clock, drain
    conf = smoke.smoke_conf("deepseek-v2-9l")
    m = conf["model"]
    w, srv = run.build(conf, smoke.smoke_mix("decode-heavy"), seed,
                       run.CompileCounter(), lambda msg: None,
                       run.module("arch", conf["arch"]))
    rng = np.random.default_rng(seed)
    reqs = [traffic.Req(rid=i, tenant=i, due=clock(), prompt_len=16,
                        out_len=40, prompt=rng.integers(
                            0, m["vocab_size"], 16, dtype=np.int32))
            for i in range(4)]
    for r in reqs:
        srv.submit(r, r.due)
    drain(srv)
    return check.gaps(run.module("reference", conf["reference"]), w, m,
                      reqs, conf["engine"]["max_seq"])["served_gap_max"]


@pytest.mark.parametrize("fault", [None, "held_zeroed",
                                   "routed_scale_dropped",
                                   "held_range_shifted"])
def test_the_comparison_sees_a_fault_in_the_held_experts(monkeypatch, fault):
    """The harness's own comparison, at the smoke run's limit, passes the
    program as it is and catches one whose held experts add nothing,
    whose routed weights lose the routed scale, or whose held range is
    off by one expert: the held experts' part of the output is large
    enough to see. Over seeds 2**31 + 0..7 the program read 0.001-0.043
    against the limit of 0.05 (the largest where bf16 rounding moved a
    token across a routing boundary) and the three faults 0.057-0.433;
    at 2**31, 0.003 and 0.18-0.32."""
    if fault:
        _fault(monkeypatch, fault)
    limit = smoke.smoke_conf("deepseek-v2-9l")["check"]["served_gap_max"]
    gap = _served_gap(2**31)
    assert (gap > limit) if fault else (gap <= limit)


def test_counters_feed_the_expert_roofline(monkeypatch):
    """The window's counters move, the held share of assignments is the
    held experts' share of the router, and moe_roofline reads them over
    a hand-made op scope."""
    ctxs = smoke.record_ctx(monkeypatch)
    _, c, _, _, e2e, per = run.load_cell(CELL)
    run.run_cell(smoke.args(), c, smoke.smoke_conf("deepseek-v2-9l"),
                 smoke.smoke_mix("decode-heavy"), e2e, per, smoke.PEAK,
                 smoke.DEVICE, lambda msg: None)
    ctx = ctxs[0]
    a, b = ctx.counters_at_open, ctx.counters_at_close
    assert b["nk_decode_cache_inplace_segments"] == 2.0
    assert b["nk_moe_experts_held"] == ARCH.SMOKE["moe"]["num_experts"]
    moved = {k: b[k] - a[k] for k in b if k.endswith("_total")}
    assert all(v > 0 for v in moved.values()), moved
    share = moved["nk_moe_assignments_held_total"] / \
        moved["nk_moe_assignments_total"]
    assert 0.02 < share < 0.2      # 4 of 64 experts, by random routing
    assert run.reader("moe_held_share.dsv2")(ctx) == pytest.approx(
        100 * share)
    lo, hi = 0.0, 1e9
    ctx.trace = {"devices": {"/device:TPU:0": {
        "XLA Modules": [["jit__decode", 10.0, 1e6]],
        "XLA Ops": [["fusion.1", 10.0, 4e5, "jit(_decode)/while/body/moe/x"],
                    ["fusion.2", 5e5, 1e5, "jit(_decode)/while/body/mla/y"]],
    }}, "host": [], "program": []}
    ctx.trace_window = (lo, hi)
    moe_roof = run.reader("moe_roofline.dsv2")(ctx)
    assert moe_roof is not None and moe_roof > 0
    assert run.reader("moe_ms.dsv2")(ctx) == pytest.approx(0.4)
    assert run.reader("mla_ms.dsv2")(ctx) == pytest.approx(0.1)
    ctx.trace["devices"]["/device:TPU:0"]["XLA Ops"] = [
        ["fusion.1", 10.0, 4e5, "jit(_decode)/while/body/dot"]]
    assert run.reader("moe_ms.dsv2")(ctx) is None
