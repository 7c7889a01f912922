"""Per-arch smoke tests: REDUCED family-preserving configs, one forward +
one train step on CPU, asserting shapes and no NaNs; plus prefill/decode
parity against the train-mode forward (teacher forcing)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig, get_smoke_config
from repro.distribution.sharding import ShardingCtx
from repro.models import (
    build_params, forward_decode, forward_prefill, forward_train,
)
from repro.train.train_loop import loss_fn

B, S = 2, 64


def _cfg(name):
    cfg = get_smoke_config(name)
    if cfg.moe is not None:   # capacity drops are path-dependent: disable
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
    return cfg


def _batch(cfg):
    batch = {"tokens": jnp.arange(B * S).reshape(B, S) % cfg.vocab_size,
             "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.encoder_layers:
        batch["frames"] = jnp.ones(
            (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16) * 0.1
    return batch


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_forward_and_train_step(name, mesh1, rcfg_small):
    cfg = _cfg(name)
    shd = ShardingCtx(mesh1)
    params = build_params(cfg, mesh1, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = jax.jit(
        lambda p, b: forward_train(p, b, cfg, shd, rcfg_small))(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    # one gradient step must produce finite grads for every leaf
    g = jax.jit(jax.grad(
        lambda p: loss_fn(p, batch, cfg, shd, rcfg_small)[0]))(params)
    for leaf in jax.tree.leaves(g):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_prefill_decode_parity(name, mesh1, rcfg_small):
    cfg = _cfg(name)
    shd = ShardingCtx(mesh1)
    params = build_params(cfg, mesh1, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, _ = jax.jit(
        lambda p, b: forward_train(p, b, cfg, shd, rcfg_small))(params, batch)
    last, caches = jax.jit(
        lambda p, t: forward_prefill(p, t, cfg, shd, rcfg_small,
                                     max_seq=S + 8,
                                     frames=batch.get("frames")))(
        params, batch["tokens"])
    np.testing.assert_allclose(np.asarray(last, np.float32),
                               np.asarray(logits[:, -1], np.float32),
                               rtol=2e-2, atol=2e-2)
    nxt = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
    dec, caches = jax.jit(
        lambda p, c, t, pos: forward_decode(p, c, t, pos, cfg, shd,
                                            rcfg_small))(
        params, caches, nxt, jnp.full((B,), S, jnp.int32))
    ext = dict(batch, tokens=jnp.concatenate([batch["tokens"], nxt], 1))
    ref, _ = jax.jit(
        lambda p, b: forward_train(p, b, cfg, shd, rcfg_small))(params, ext)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(ref[:, -1], np.float32),
                               rtol=1e-1, atol=1e-1)


def test_param_counts_match_analytic():
    """Analytic num_params (used by the roofline) vs materialized params
    (deepseek-v2: latent attention with the low-rank q)."""
    for name in ("llama3.2-3b", "internlm2-1.8b", "mamba2-370m",
                 "deepseek-v2-236b"):
        cfg = get_smoke_config(name)
        from repro.launch.mesh import make_single_device_mesh
        mesh = make_single_device_mesh()
        params = build_params(cfg, mesh, jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree.leaves(params))
        analytic = cfg.num_params()
        # padding of heads makes materialized >= analytic; within 25%
        assert analytic <= n * 1.05
        assert n <= analytic * 1.3, (name, n, analytic)


def _decode_two_steps(cfg, mesh, rcfg, params, caches, tokens, pos):
    """Two decode steps, the second from the first's cache (jitted afresh,
    so a patched ``decode_writes_in_place`` is seen)."""
    from repro.models import model as model_mod
    shd = ShardingCtx(mesh)
    step = jax.jit(lambda p, c, t, q: model_mod.forward_decode(
        p, c, t, q, cfg, shd, rcfg))
    out = []
    for q in (pos, jnp.minimum(pos + 1, caches[0]["k"].shape[2] - 1)):
        logits, caches = step(params, caches, tokens, q)
        out.append((logits, caches))
        tokens = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    return out


def _random_caches(cfg, batch, max_seq):
    from repro.models import cache_schema
    from repro.distribution.sharding import init_params
    caches = init_params(cache_schema(cfg, batch, max_seq),
                         jax.random.PRNGKey(1))
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    return jax.tree.map(lambda a: jax.random.normal(
        next(keys), a.shape, jnp.float32).astype(a.dtype), caches)


@pytest.mark.parametrize("name", ["internlm2-1.8b", "arctic-480b"])
def test_in_place_decode_write_matches_masked_select(name, mesh1, rcfg_small,
                                                     monkeypatch):
    """On one device the decode writes its new K/V rows into the stacked
    cache in place, and its logits and caches are bit-identical to the
    masked-select write's, over two steps with slots at the first, a middle
    and the last position."""
    from repro.models import build_schedule, model as model_mod
    cfg = _cfg(name)
    max_seq = 64
    params = build_params(cfg, mesh1, jax.random.PRNGKey(0))
    caches = _random_caches(cfg, 3, max_seq)
    tokens = jnp.array([[1], [2], [3]], jnp.int32)
    pos = jnp.array([0, max_seq // 2 - 1, max_seq - 1], jnp.int32)
    shd = ShardingCtx(mesh1)
    assert [model_mod.decode_writes_in_place(s, c, shd, rcfg_small)
            for s, c in zip(build_schedule(cfg), caches)] == [True]
    in_place = _decode_two_steps(cfg, mesh1, rcfg_small, params, caches,
                                 tokens, pos)
    monkeypatch.setattr(model_mod, "decode_writes_in_place",
                        lambda *a: False)
    masked = _decode_two_steps(cfg, mesh1, rcfg_small, params, caches,
                               tokens, pos)
    for (la, ca), (lb, cb) in zip(in_place, masked):
        np.testing.assert_array_equal(np.asarray(la, np.float32),
                                      np.asarray(lb, np.float32))
        for a, b in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    # the step wrote exactly one row per slot and layer
    first = in_place[0][1][0]["k"]
    changed = np.any(np.asarray(first != caches[0]["k"]), axis=(3, 4))
    expect = np.zeros_like(changed)
    expect[:, np.arange(3), np.asarray(pos)] = True
    np.testing.assert_array_equal(changed, expect)


@pytest.mark.parametrize("name", ["internlm2-1.8b", "arctic-480b"])
def test_model_sharded_decode_keeps_masked_select(name, mesh1, rcfg_small):
    """On a mesh whose model axis is 2 the predicate picks the masked select
    (the cache's sequence axis is sharded), and the step still matches the
    one-device in-place step: the cache outside the new rows unchanged, the
    new rows and the logits within the bf16 tolerance of the prefill/decode
    parity test above (the context-parallel softmax rounds otherwise)."""
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_schedule, model as model_mod
    cfg = _cfg(name)
    max_seq = 64
    mesh2 = make_host_mesh(1, 2)
    params = build_params(cfg, mesh1, jax.random.PRNGKey(0))
    caches = _random_caches(cfg, 3, max_seq)
    tokens = jnp.array([[1], [2], [3]], jnp.int32)
    pos = jnp.array([0, max_seq // 2 - 1, max_seq - 1], jnp.int32)
    assert not any(model_mod.decode_writes_in_place(
        s, c, ShardingCtx(mesh2), rcfg_small)
        for s, c in zip(build_schedule(cfg), caches))
    one = _decode_two_steps(cfg, mesh1, rcfg_small, params, caches,
                            tokens, pos)
    two = _decode_two_steps(cfg, mesh2, rcfg_small, params, caches,
                            tokens, pos)
    rows = (slice(None), np.arange(3), np.asarray(pos))
    for (la, ca), (lb, cb) in zip(one, two):
        np.testing.assert_allclose(np.asarray(lb, np.float32),
                                   np.asarray(la, np.float32),
                                   rtol=1e-1, atol=1e-1)
        for name_ in ("k", "v"):
            a = np.asarray(ca[0][name_], np.float32)
            b = np.asarray(cb[0][name_], np.float32)
            np.testing.assert_allclose(b[rows], a[rows], rtol=1e-1,
                                       atol=1e-1)
    first = np.asarray(two[0][1][0]["k"], np.float32)
    kept = np.ones(first.shape[:3], bool)
    kept[rows] = False
    np.testing.assert_array_equal(
        first[kept], np.asarray(caches[0]["k"], np.float32)[kept])


@pytest.mark.parametrize("name,layers", [
    ("internlm2-1.8b", 24), ("arctic-480b", 35), ("deepseek-v2-236b", 0),
    ("hymba-1.5b", 0), ("whisper-small", 0), ("mamba2-370m", 0)])
def test_decode_kernel_layers(name, layers):
    """On a TPU the flash-decode kernel runs in every layer whose segment
    writes a stacked K/V cache in place (dense and expert GQA); latent
    attention, ring windows beside SSM state, cross-attention and SSM
    state keep the jnp path, as does every layer on the CPU, on a mesh
    that shards the cache's sequence or its slots, and on a cache not made
    of whole blocks."""
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import decode_kernel_layers
    cfg, rcfg, one = get_config(name), RunConfig(), ShardingCtx(None)
    assert decode_kernel_layers(cfg, one, rcfg, 2048, "tpu") == layers
    assert decode_kernel_layers(cfg, one, rcfg, 2048, "cpu") == 0
    for data, model in ((1, 2), (2, 1)):
        assert decode_kernel_layers(cfg, ShardingCtx(make_host_mesh(
            data, model)), rcfg, 2048, "tpu") == 0
    assert decode_kernel_layers(cfg, one, rcfg, 2048 + 64, "tpu") == 0


def _tpu_branches(monkeypatch, block):
    """Take every platform choice as for a TPU, with the Pallas kernels
    interpreted, so the decode attends with the flash-decode kernel on the
    CPU, reading the cache in blocks of ``block`` positions; and let the
    engine count the layers that run it as on a TPU."""
    from repro.kernels import decode_attention as kernel_mod
    from repro.serve import engine as engine_mod

    def platform_dependent(*args, **branches):
        return branches.get("tpu", branches.get("cpu"))(*args)
    monkeypatch.setattr(jax.lax, "platform_dependent", platform_dependent)
    monkeypatch.setattr(kernel_mod, "kv_block_for",
                        lambda seq, kv_block=None: min(block, seq))
    monkeypatch.setattr(engine_mod, "lowering_platform", lambda mesh: "tpu")


def test_decode_kernel_keeps_the_engines_greedy_tokens(mesh1, rcfg_small,
                                                       monkeypatch):
    """internlm2 at smoke width, served by the engine: on the CPU no layer
    runs the flash-decode kernel, so the engine exports none of its
    counters; with
    the kernel in every layer (interpreted, 16-position blocks, so slots
    cross blocks) the greedy tokens are the jnp attention's, and the
    counters sum the blocks each step reads at the positions it sent."""
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import Request
    cfg, max_seq, block = get_smoke_config("internlm2-1.8b"), 64, 16
    params = build_params(cfg, mesh1, jax.random.PRNGKey(0))

    def serve():
        eng = ServeEngine(cfg, rcfg_small, mesh1, params=params,
                          batch_slots=3, max_seq=max_seq)
        sent, decode = [], eng._decode

        def recording_decode(params, caches, tokens, pos):
            sent.append(np.asarray(pos))
            return decode(params, caches, tokens, pos)
        eng._decode = recording_decode
        for i, (n, out) in enumerate(((20, 12), (5, 30), (33, 8), (3, 9))):
            eng.submit(Request(tenant_id=i % 2, prompt=[
                (7 * i + 3 * t) % cfg.vocab_size for t in range(n)],
                max_new_tokens=out, req_id=i))
        eng.run_until_drained()
        return ({r.req_id: r.generated for r in eng.completed},
                eng.counters(), sent)

    want, plain, _ = serve()
    assert plain == {"nk_decode_cache_inplace_segments": 1.0}

    _tpu_branches(monkeypatch, block)
    got, counted, sent = serve()
    assert got == want and len(got) == 4
    layers = cfg.num_layers
    assert counted["nk_decode_attention_kernel_layers"] == layers
    assert counted["nk_decode_kv_blocks_read_total"] == layers * sum(
        int(((pos // block + 1) * (pos > 0)).sum()) for pos in sent)
    assert counted["nk_decode_kv_blocks_total"] == \
        layers * len(sent) * 3 * max_seq // block
    assert 0 < counted["nk_decode_kv_blocks_read_total"] \
        < counted["nk_decode_kv_blocks_total"]
