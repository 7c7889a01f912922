"""Nemotron-H's mechanisms in the program: a schedule that follows the
published layer pattern, sigmoid routing that chooses with the correction
bias and weighs without it, B/C in groups through the chunked scan and the
one-token update, conv tails of prompts shorter than the conv, and slots
that do not see each other."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config, get_smoke_config
from repro.configs.base import MoEConfig
from repro.distribution.sharding import ShardingCtx
from repro.models import build_params, model as model_mod
from repro.models.moe import route_topk
from repro.models.ssm import ssd_chunked, ssd_decode_step

NAME = "nemotron-3-nano-30b-a3b"


def _cut(blocks=20):
    cfg = get_config(NAME)
    return dataclasses.replace(cfg, num_layers=blocks, moe=dataclasses.replace(
        cfg.moe, num_experts=16))


def _smoke(name=NAME):
    """The smoke config; an expert layer holds a share of its router's
    experts (dropless), so no capacity couples a call's tokens."""
    cfg = get_smoke_config(name)
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=2, router_experts=8, top_k=3))


def test_the_schedule_follows_the_layer_pattern():
    """One segment of one block per letter (the pattern alternates); M
    blocks keep state and conv tails, * blocks K/V, E blocks nothing; the
    analytic parameter count follows the blocks."""
    cfg = _cut()
    segs = model_mod.build_schedule(cfg)
    assert "".join({"ssm": "M", "experts": "E", "attn": "*"}[s.kind]
                   for s in segs) == "MEMEM*EMEMEM*EMEMEM*"
    assert all(s.count == 1 for s in segs)
    caches = model_mod.cache_schema(cfg, 4, 2048)
    for s, c in zip(segs, caches):
        want = {"ssm": {"state", "conv_x", "conv_B", "conv_C"},
                "attn": {"k", "v"}, "experts": None}[s.kind]
        assert (c if c is None else set(c)) == want
    state = next(c for c in caches if c is not None and "state" in c)
    assert state["state"].shape == (1, 4, 64, 64, 128)
    assert state["state"].dtype == "float32"
    assert state["conv_x"].shape == (1, 4, 3, 4096)
    assert state["conv_B"].shape == (1, 4, 3, 8 * 128)
    assert cfg.num_params() == 2_563_133_248
    assert get_config(NAME).num_layers_moe() == 23
    one = ShardingCtx(None)
    assert model_mod.decode_kernel_layers(cfg, one, RunConfig(), 2048,
                                          "tpu") == 3
    assert model_mod.decode_kernel_layers(cfg, one, RunConfig(), 2048,
                                          "cpu") == 0


def test_sigmoid_routing_chooses_with_the_bias_and_weighs_without():
    """Four experts, top-2. Sigmoid scores 0.80, 0.70, 0.60, 0.50; the
    bias (0, 0, 0.25, 0) makes expert 2 the first choice: experts 2 and 0
    are taken, weighted by their scores alone (0.60 and 0.80), divided by
    their sum and scaled by 2.5."""
    m = MoEConfig(num_experts=4, top_k=2, expert_ff=8, score="sigmoid",
                  routed_scale=2.5)
    p = np.array([0.8, 0.7, 0.6, 0.5])
    w_r = jnp.zeros((4, 4)).at[0].set(jnp.log(p / (1 - p)))
    x = jnp.eye(4)[:1]                                 # logits = w_r[0]
    bias = jnp.array([0.0, 0.0, 0.25, 0.0])
    gate, eidx, _ = route_topk(w_r, x, m, bias)
    assert np.asarray(eidx[0]).tolist() == [2, 0]
    np.testing.assert_allclose(np.asarray(gate[0]),
                               2.5 * np.array([0.6, 0.8]) / 1.4, rtol=1e-5)
    gate, eidx, _ = route_topk(w_r, x, m, jnp.zeros(4))
    assert np.asarray(eidx[0]).tolist() == [0, 1]


def _recurrence(xdt, dA, B, C):
    """h_t = exp(dA_t) h_{t-1} + xdt_t B_t^T, y_t = h_t C_t, head h
    reading group h // (H / G), in float64."""
    b, l, h, p = xdt.shape
    g, n = B.shape[2:]
    grp = np.arange(h) // (h // g)
    B, C = np.asarray(B, np.float64)[:, :, grp], \
        np.asarray(C, np.float64)[:, :, grp]
    state = np.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        state = state * np.exp(np.asarray(dA[:, t], np.float64))[
            ..., None, None] + np.asarray(xdt[:, t], np.float64)[
            ..., None] * B[:, t, :, None, :]
        ys.append(np.einsum("bhpn,bhn->bhp", state, C[:, t]))
    return np.stack(ys, 1), state


@pytest.mark.parametrize("l,chunk", [(64, 16), (45, 16)])
def test_grouped_scan_and_update_match_the_recurrence(l, chunk):
    """Four heads reading two B/C groups: the chunked scan (padded where
    the length is no chunk multiple) and the one-token update continuing
    from its state agree with the recurrence."""
    b, h, p, g, n = 2, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    xdt = jax.random.normal(ks[0], (b, l + 3, h, p)) * 0.2
    dA = -jnp.abs(jax.random.normal(ks[1], (b, l + 3, h))) * 0.2
    B = jax.random.normal(ks[2], (b, l + 3, g, n)) * 0.4
    C = jax.random.normal(ks[3], (b, l + 3, g, n)) * 0.4
    y_ref, st_ref = _recurrence(xdt, dA, B, C)
    y, st = ssd_chunked(xdt[:, :l], dA[:, :l], B[:, :l], C[:, :l], chunk)
    np.testing.assert_allclose(np.asarray(y), y_ref[:, :l], rtol=2e-3,
                               atol=2e-3)
    for t in range(l, l + 3):
        # dt = 1 and A = dA: the update's decay exp(dt A) is exp(dA)
        y_t, st = ssd_decode_step(xdt[:, t], jnp.ones((b, h)), dA[:, t],
                                  B[:, t], C[:, t], st)
        np.testing.assert_allclose(np.asarray(y_t), y_ref[:, t], rtol=2e-3,
                                   atol=2e-3)
    np.testing.assert_allclose(np.asarray(st), st_ref, rtol=2e-3, atol=2e-3)


def _serve(cfg, params, prompts, max_new=6, stagger=None):
    """Greedy tokens of each request served by one engine; ``stagger``
    (request index -> decode steps) holds a request back until the engine
    has taken that many steps."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import Request
    eng = ServeEngine(cfg, RunConfig(), make_host_mesh(1, 1), params=params,
                      batch_slots=3, max_seq=64)
    reqs = [Request(tenant_id=0, prompt=list(pr), max_new_tokens=max_new,
                    req_id=i) for i, pr in enumerate(prompts)]
    stagger = stagger or {}
    for i, r in enumerate(reqs):
        if i not in stagger:
            eng.submit(r)
    while eng.scheduler.pending() or any(s.active for s in eng.slots) \
            or any(eng.steps < k for k in stagger.values()):
        for i, k in stagger.items():
            if eng.steps == k:
                eng.submit(reqs[i])
        eng.step()
    return {r.req_id: r.generated for r in eng.completed}


@pytest.mark.parametrize("name", ["mamba2-370m", NAME])
def test_prompts_shorter_than_the_conv_serve_through_the_engine(name):
    """Prompts of 1 and 2 tokens (the conv's tails are 3): their tails are
    preceded by zeros and install into the slot, and each served token is
    the train forward's greedy choice over the prompt and the tokens
    before it, up to a bf16 near-tie."""
    from repro.models import forward_train
    cfg = _smoke(name)
    params = build_params(cfg, None, jax.random.PRNGKey(0))
    got = _serve(cfg, params, [[5], [7, 9]])
    shd, rcfg = ShardingCtx(None), RunConfig()
    for i, prompt in enumerate(([5], [7, 9])):
        seq = prompt + got[i][:-1]
        logits, _ = forward_train(params, {"tokens": jnp.asarray([seq])},
                                  cfg, shd, rcfg)
        lg = np.asarray(logits[0, len(prompt) - 1:], np.float32)
        served = lg[np.arange(len(got[i])), got[i]]
        assert len(got[i]) == 6
        assert np.max(lg.max(-1) - served) < 2e-2


def test_a_slot_does_not_see_its_neighbours():
    """A request's greedy tokens are the same served alone and beside
    other requests admitted into the other slots before and while it
    decodes (every block kind, a held share of the experts, so no
    capacity couples the slots)."""
    cfg = _smoke()
    params = build_params(cfg, None, jax.random.PRNGKey(1))
    alone = _serve(cfg, params, [[3, 1, 4, 1, 5, 9, 2, 6]], max_new=12)
    crowd = _serve(cfg, params, [[2, 7], [3, 1, 4, 1, 5, 9, 2, 6],
                                 list(range(40, 77)), [11] * 5],
                   max_new=12, stagger={2: 3, 3: 7})
    assert crowd[1] == alone[0]
