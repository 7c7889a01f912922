"""Nemotron-3-Nano at one chip's share (bench/configs/nemotron-3-nano-20l.json):
the program against the plain reference (bench/reference/mamba_moe.py,
whose Mamba-2 mixer runs the sequential recurrence) on seeded random
weights at the architecture's smoke widths (every block kind, 2 B/C
groups, 4 of 32 experts held), the share against the uncut layer, the
file against the program's published widths, the architecture module
against the program's tree, and a smoke run of the cell."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke
from bench import run, weights
from bench.client import program_config

CELL = "nemotron-3-nano-20l.decode-heavy"
CONF = json.loads((smoke.ROOT / "bench" / "configs" /
                   "nemotron-3-nano-20l.json").read_text())
ARCH = run.module("arch", "mamba_moe")
REF = run.module("reference", "mamba_moe")

# Program (bf16 weights and activations, f32 accumulation and f32 state)
# against the float32 reference, largest |logit difference| over every
# vocabulary entry of a prefill and 12 decode steps, logits of standard
# deviation near 1: 0.038-0.067 over seeds 2**31 + 0..7 with 20-token
# prompts, 0.043-0.116 with 45-token prompts (the largest where bf16
# rounding carried a token across a top-6 routing boundary); the float8
# control reads 0.329-0.551 on the same sequences. 0.2 sits 1.7 times
# above the largest bf16 reading and 1.6 times below the smallest control.
LOGIT_TOL = 0.2


def _smoke_model():
    m = dict(CONF["model"])
    m.update(ARCH.SMOKE)
    return m


def _full_logits(w, m, seq, quant=None):
    v = m["vocab_size"]
    probe = jnp.broadcast_to(jnp.arange(v)[:, None], (v, seq.shape[0]))
    return np.asarray(REF.stats(w, m, jnp.asarray(seq), probe,
                                quant=quant)[2]).T          # (S, V)


# 20 tokens are less than a chunk (32); 45 are one chunk and a part
@pytest.mark.parametrize("seed,prompt", [(2**31, 20), (2**31 + 2, 45)])
def test_prefill_and_decode_through_the_state_match_the_reference(
        seed, prompt):
    """Two sequences prefilled to ``prompt`` tokens (padded to the SSD's
    chunk), then 12 decode steps through the state, conv tails and K/V,
    teacher-forced: every logit within LOGIT_TOL of the reference's full
    forward at that position; the float8 control is not."""
    from repro.configs import RunConfig
    from repro.distribution.sharding import ShardingCtx
    from repro.models.model import forward_decode, forward_prefill
    m = _smoke_model()
    cfg = program_config(m)
    w = weights.make_weights(ARCH.layout(m), "bfloat16", seed)
    p = ARCH.program_params(w, m)
    S, P = 64, prompt
    toks = np.random.default_rng(seed).integers(
        0, m["vocab_size"], (2, S)).astype(np.int32)
    ref = [_full_logits(w, m, toks[b]) for b in range(2)]
    shd, rcfg = ShardingCtx(None), RunConfig()
    last, caches = forward_prefill(p, jnp.asarray(toks[:, :P]), cfg, shd,
                                   rcfg, max_seq=S)
    got = [(P - 1, np.asarray(last, np.float32))]
    for t in range(P, P + 12):
        lg, caches = forward_decode(p, caches, jnp.asarray(toks[:, t:t + 1]),
                                    jnp.full((2,), t, jnp.int32), cfg, shd,
                                    rcfg)
        got.append((t, np.asarray(lg, np.float32)))
    err = max(np.max(np.abs(g[b] - ref[b][t])) for t, g in got
              for b in range(2))
    assert err < LOGIT_TOL
    f8 = _full_logits(w, m, toks[0], quant="fp8")
    assert np.max(np.abs(f8[P - 1:P + 13] - ref[0][P - 1:P + 13])) \
        > LOGIT_TOL


def test_the_shares_add_up_to_the_uncut_layer():
    """The router's 32 experts split over 8 shares of 4, sigmoid scores
    chosen with the correction bias: the shares' routed parts, with the
    shared expert counted once, equal the reference's expert layer
    holding all 32 (float32 throughout); routing without the bias, or
    weighting by score + bias, does not."""
    from repro.configs import RunConfig
    from repro.distribution.sharding import ShardingCtx
    from repro.models.moe import apply_moe
    m = _smoke_model()
    cfg = dataclasses.replace(program_config(m), param_dtype="float32",
                              dtype="float32")
    mo = m["moe"]
    R, E = mo["router_experts"], mo["num_experts"]
    d, ff, sff = m["d_model"], mo["expert_ff"], mo["shared_ff"]
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    full = {"router": jax.random.normal(keys[0], (d, R)) * d ** -0.5,
            "correction_bias": jax.random.normal(keys[1], (R,)) * 0.05,
            "w_in": jax.random.normal(keys[2], (R, d, ff)) * d ** -0.5,
            "w_out": jax.random.normal(keys[3], (R, ff, d)) * ff ** -0.5,
            "shared_w_in": jax.random.normal(keys[4], (d, sff)) * d ** -0.5,
            "shared_w_out": jax.random.normal(keys[5], (sff, d))
            * sff ** -0.5}
    x = jax.random.normal(keys[6], (1, 24, d))
    shd, rcfg = ShardingCtx(None), RunConfig()

    def shares(bias):
        total = 0.0
        for first in range(0, R, E):
            c = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, first_expert=first))
            p = {"router": full["router"], "correction_bias": bias,
                 "shared": {"w_in": full["shared_w_in"],
                            "w_out": full["shared_w_out"]},
                 **{k: full[k][first:first + E] for k in ("w_in", "w_out")}}
            total = total + apply_moe(p, x, c, shd, rcfg)[0][0]
        return total
    uncut = dict(mo, num_experts=R, first_expert=0)
    shared_part = REF.experts(x[0], dict(full, w_out=0 * full["w_out"]),
                              uncut, None)
    whole = REF.experts(x[0], full, uncut, None)
    got = shares(full["correction_bias"]) - (R // E - 1) * shared_part
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-4, atol=1e-4)
    unbiased = shares(0 * full["correction_bias"]) \
        - (R // E - 1) * shared_part
    assert np.max(np.abs(np.asarray(unbiased - whole))) > 1e-2

    def weighted_with_bias(b, router, bias, mo, quant=None):
        s = jax.nn.sigmoid(b @ router) + bias
        top, idx = jax.lax.top_k(s, mo["top_k"])
        top = top / jnp.sum(top, -1, keepdims=True) * mo["routed_scale"]
        hit = idx[..., None] == jnp.arange(mo["num_experts"])
        return jnp.sum(jnp.where(hit, top[..., None], 0.0), axis=1)
    route = REF.route
    REF.route = weighted_with_bias
    try:
        wrong = REF.experts(x[0], full, uncut, None)
    finally:
        REF.route = route
    assert np.max(np.abs(np.asarray(wrong - whole))) > 1e-3


def test_the_file_gives_the_published_widths():
    """The program's registered config is the file's catalog keys: the
    pattern, the mixer's heads, groups, state, conv and chunk, the
    experts and their routing, the attention's heads; the file's cuts
    are exactly the layers and the experts held."""
    from repro.configs import get_config
    pub = get_config("nemotron-3-nano-30b-a3b")
    cfg = program_config(CONF["model"])
    assert pub.layer_pattern == CONF["hybrid_override_pattern"]
    assert pub.num_layers == 52 and cfg.num_layers == 20
    assert cfg.blocks == "MEMEM*EMEMEM*EMEMEM*"
    assert (cfg.d_model, cfg.vocab_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.norm_eps, cfg.tie_embeddings) == (
        CONF["hidden_size"], CONF["vocab_size"],
        CONF["num_attention_heads"], CONF["num_key_value_heads"],
        CONF["head_dim"], CONF["layer_norm_epsilon"],
        CONF["tie_word_embeddings"])
    assert cfg.rope_theta == 0.0          # NoPE: no rotary embedding is read
    s = cfg.ssm
    assert (s.num_heads, s.head_dim, s.n_groups, s.state_dim, s.conv_width,
            s.conv_bias, s.chunk) == (
        CONF["mamba_num_heads"], CONF["mamba_head_dim"], CONF["n_groups"],
        CONF["ssm_state_size"], CONF["conv_kernel"], CONF["use_conv_bias"],
        CONF["chunk_size"])
    assert s.d_inner(cfg.d_model) == 4096
    assert (s.dt_min, s.dt_max) == (CONF["time_step_min"],
                                    CONF["time_step_max"])
    mo = cfg.moe
    assert (mo.router_experts, mo.num_experts, mo.first_expert, mo.top_k,
            mo.expert_ff, mo.num_shared_experts, mo.shared_ff,
            mo.routed_scale, mo.renormalize_top_k, mo.score,
            mo.expert_groups) == (
        128, CONF["n_routed_experts"], 0, CONF["num_experts_per_tok"],
        CONF["moe_intermediate_size"], CONF["n_shared_experts"],
        CONF["moe_shared_expert_intermediate_size"],
        CONF["routed_scaling_factor"], CONF["norm_topk_prob"], "sigmoid",
        CONF["n_group"])
    assert cfg.activation == CONF["mlp_hidden_act"] == "relu2"
    assert {k: (v["published"], v["here"]) for k, v in
            CONF["reduced"].items()} == {"num_hidden_layers": (52, 20),
                                         "n_routed_experts": (128, 16)}
    assert CONF["num_hidden_layers"] == 20


def test_layout_is_the_programs_tree():
    """At smoke widths the arch module's tensors, through program_params,
    have exactly the program's model_schema shapes, share the drawn
    arrays, and its parameter count is the tree's."""
    from repro.distribution.sharding import abstract_params
    from repro.models.model import model_schema
    m = _smoke_model()
    w = weights.make_weights(ARCH.layout(m), "bfloat16", 2**31 + 5)
    p = ARCH.program_params(w, m)
    want = abstract_params(model_schema(program_config(m), None))
    assert jax.tree.structure(p) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert p["segments"][1]["moe"]["w_in"] is w["blocks"]["01"]["w_in"]
    n = sum(x.size for x in jax.tree.leaves(want))
    assert ARCH.param_count(m) == n == program_config(m).num_params()


def test_mixer_constants_follow_the_published_initialisation():
    """A in [1, 16]; softplus(dt_bias) log-uniform in [0.001, 0.1] (the
    floor of 1e-4 lies below it)."""
    z = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    a_log, dt_bias = ARCH.mixer_constants({"a_draw": z, "dt_draw": -z})
    A = np.exp(np.asarray(a_log))
    dt = np.asarray(jax.nn.softplus(dt_bias))
    assert 1.0 <= A.min() < 1.1 and 15.9 < A.max() <= 16.0
    assert 0.001 <= dt.min() < 0.0012 and 0.09 < dt.max() <= 0.1001
    np.testing.assert_allclose(np.median(np.log(dt)),
                               np.log(np.sqrt(0.001 * 0.1)), atol=0.1)


def test_full_width_counts():
    """At the published widths: 2,563,133,248 parameters as laid out;
    a slot's state 2,097,152 bytes a mixer and its conv tails 36,864;
    a decode step at 256 live slots reads and writes 9.83 GB of state and
    tails in its 9 mixers, beside their 0.70 GB of weights, and reads
    about 4.4 GB of weights in all."""
    m = CONF["model"]
    assert ARCH.param_count(m) == 2_563_133_248
    assert sum(int(np.prod(s)) for _, s, _ in ARCH.layout(m)) == \
        ARCH.param_count(m)
    assert ARCH.slot_state_bytes(m) == 2_097_152 + 36_864
    c = ARCH.ssm_call(m, 256)
    assert 9 * 256 * 2 * ARCH.slot_state_bytes(m) == 9_833_545_728
    assert 10.4e9 < c["bytes"] < 10.7e9
    assert 4.3e9 < ARCH.weight_bytes(m, 256) < 4.6e9
    assert ARCH.held_per_token(m) == 6 * 16 / 128
    d = ARCH.decode_call(m, [512] * 256)
    assert 9_833_545_728 + ARCH.weight_bytes(m, 256) < d["bytes"] < 15e9


def test_smoke_run_reports_every_metric():
    """The cell's harness at smoke widths: the window is served and the
    ledger is exact. Whether the served tokens are correct is judged on a
    fixed set of requests served to their end
    (test_the_comparison_sees_a_fault), not on however many a wall-clock
    window finishes."""
    _, c, _, _, e2e, per = run.load_cell(CELL)
    out = run.run_cell(smoke.args(), c, smoke.smoke_conf(
        "nemotron-3-nano-20l"), smoke.smoke_mix("decode-heavy"), e2e, per,
        smoke.PEAK, smoke.DEVICE, lambda msg: None)
    assert out["attempted"] > 0
    assert out["checks"]["ledger_gap"]["value"] == 0
    assert out["checks"]["lost"]["value"] == 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}


def _fault(monkeypatch, fault):
    """Serve with one fault in the program's parameters; the reference
    (and the weights it reads) stay whole."""
    arch = run.module("arch", "mamba_moe")
    params = arch.program_params

    def program_params(w, m):
        segs = list(params(w, m)["segments"])
        for i, seg in enumerate(segs):
            if fault == "bias_dropped" and "moe" in seg:
                moe = seg["moe"]
                segs[i] = dict(seg, moe=dict(moe, correction_bias=jnp.zeros_like(
                    moe["correction_bias"])))
            if fault == "one_group" and "ssm" in seg:
                # every head reads B/C group 0
                n, g = m["ssm"]["state_dim"], m["ssm"]["n_groups"]
                ssm = dict(seg["ssm"])
                for k in ("w_B", "w_C", "conv_B", "conv_C", "conv_bias_B",
                          "conv_bias_C"):
                    ssm[k] = jnp.tile(ssm[k][..., :n],
                                      (1,) * (ssm[k].ndim - 1) + (g,))
                segs[i] = dict(seg, ssm=ssm)
        return dict(params(w, m), segments=tuple(segs))
    monkeypatch.setattr(arch, "program_params", program_params)


PROMPTS = (1, 2, 5, 13, 21, 30, 37)


def _served_gap(seed, control=False):
    """Serve four smoke requests to the end through the cell's serving
    path (prompts of 1, 2, 21 and 37 tokens: shorter than the conv's
    tails, and not multiples of the chunk) and return the harness's
    comparison (``bench/check.py`` ``gaps``) of every token served."""
    from bench import check, traffic
    from bench.client import clock, drain
    conf = smoke.smoke_conf("nemotron-3-nano-20l")
    m = conf["model"]
    w, srv = run.build(conf, smoke.smoke_mix("decode-heavy"), seed,
                       run.CompileCounter(), lambda msg: None,
                       run.module("arch", conf["arch"]))
    rng = np.random.default_rng(seed)
    reqs = [traffic.Req(rid=i, tenant=i, due=clock(), prompt_len=n,
                        out_len=24, prompt=rng.integers(
                            0, m["vocab_size"], n, dtype=np.int32))
            for i, n in enumerate(PROMPTS)]
    for r in reqs:
        srv.submit(r, r.due)
    drain(srv)
    assert all(len(r.served.generated) == 24 for r in reqs)
    g = check.gaps(run.module("reference", conf["reference"]), w, m, reqs,
                   conf["engine"]["max_seq"], control=control)
    return (g["served_gap_max"], g["control_gap_max"]) if control \
        else g["served_gap_max"]


@pytest.mark.parametrize("fault", [None, "bias_dropped", "one_group",
                                   "float8"])
def test_the_comparison_sees_a_fault(monkeypatch, fault):
    """The harness's own comparison, at the smoke run's limit (0.05),
    passes the program as it is and catches one that chooses its experts
    without the correction bias, one whose heads all read B/C group 0, and
    the float8 control in the served tokens' place. Over seeds
    2**31 + 0..5 the program read 0.0025-0.0264, the bias dropped
    0.071-0.134, one group 0.068-0.268 and the control 0.249-0.449."""
    if fault in ("bias_dropped", "one_group"):
        _fault(monkeypatch, fault)
    limit = smoke.smoke_conf("nemotron-3-nano-20l")["check"][
        "served_gap_max"]
    if fault == "float8":
        gap = _served_gap(2**31, control=True)[1]
    else:
        gap = _served_gap(2**31)
    assert (gap > limit) if fault else (gap <= limit)


def test_counters_feed_the_readers(monkeypatch):
    """The window's counters move; the engine holds 3 mixers' state; the
    held share of assignments is near the held experts' share of the
    router; the scoped readers read a hand-made op scope and return
    nothing where it is absent."""
    ctxs = smoke.record_ctx(monkeypatch)
    _, c, _, _, e2e, per = run.load_cell(CELL)
    conf = smoke.smoke_conf("nemotron-3-nano-20l")
    run.run_cell(smoke.args(), c, conf, smoke.smoke_mix("decode-heavy"),
                 e2e, per, smoke.PEAK, smoke.DEVICE, lambda msg: None)
    ctx = ctxs[0]
    m = conf["model"]
    a, b = ctx.counters_at_open, ctx.counters_at_close
    assert b["nk_decode_cache_inplace_segments"] == 1.0
    assert b["nk_ssm_layers"] == 3.0
    assert b["nk_ssm_state_bytes"] == 3 * conf["engine"]["batch_slots"] \
        * ARCH.slot_state_bytes(m)
    assert b["nk_moe_experts_held"] == ARCH.SMOKE["moe"]["num_experts"]
    moved = {k: b[k] - a[k] for k in b if k.endswith("_total")}
    assert all(v > 0 for v in moved.values()), moved
    share = moved["nk_moe_assignments_held_total"] / \
        moved["nk_moe_assignments_total"]
    assert 0.02 < share < 0.4      # 4 of 32 experts, by random routing
    assert run.reader("moe_held_share.nemo")(ctx) == pytest.approx(
        100 * share)
    ctx.trace = {"devices": {"/device:TPU:0": {
        "XLA Modules": [["jit__decode", 10.0, 1e6]],
        "XLA Ops": [
            ["fusion.1", 10.0, 4e5, "jit(_decode)/checkpoint/moe/experts/x"],
            ["fusion.2", 5e5, 2e5, "jit(_decode)/checkpoint/ssm/update/y"],
            ["fusion.3", 8e5, 1e5, "jit(_decode)/checkpoint/attn/z"]],
    }}, "host": [], "program": []}
    ctx.trace_window = (0.0, 1e9)
    assert run.reader("moe_ms.nemo")(ctx) == pytest.approx(0.4)
    assert run.reader("ssm_ms.nemo")(ctx) == pytest.approx(0.2)
    assert run.reader("attn_ms.nemo")(ctx) == pytest.approx(0.1)
    assert run.reader("moe_roofline.nemo")(ctx) > 0
    ctx.trace["devices"]["/device:TPU:0"]["XLA Ops"] = [
        ["fusion.1", 10.0, 4e5, "jit(_decode)/checkpoint/dot"]]
    for name in ("moe_ms.nemo", "ssm_ms.nemo", "ssm_roofline.nemo",
                 "moe_roofline.nemo", "attn_ms.nemo"):
        assert run.reader(name)(ctx) is None, name
