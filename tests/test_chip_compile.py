"""Compiles for a described TPU v5e: what the chip's compiler would refuse.

Every program here is built for a ``v5e:2x2`` topology that is described,
not attached: shapes only, nothing runs. The tests pin what the serve path,
the control tick, the NSM stacks and the Pallas kernels need to lower for
the chip at real widths: internlm2-1.8b's decode and prefill within one
chip's 16 GiB, DeepSeek-V2's decode at one chip's share with its latent
cache written in place, the engine's install of a prefill cache into its
slot in place for both, the fused tick at 100k tenants, every psum routing
policy on a (pod=2, data=2) mesh, and each kernel as a Mosaic
``tpu_custom_call``.

The topology is described inside a module fixture (never at import), and
the tests skip from there where the TPU compiler is not installed. JAX's
persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import numpy as np
import pytest

GIB = 1 << 30
V5E_HBM = 16 * GIB


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def pod_mesh(topo, no_persistent_cache):
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices).reshape(2, 2, 1),
                ("pod", "data", "model"))


def _on(mesh, tree):
    """ShapeDtypeStructs of ``tree``, replicated over ``mesh``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), tree)


@pytest.fixture(scope="module")
def internlm2(one_chip):
    from repro.configs import RunConfig, get_config
    from repro.distribution.sharding import ShardingCtx, abstract_params
    from repro.models.model import model_schema
    cfg = get_config("internlm2-1.8b")
    params = _on(one_chip, abstract_params(model_schema(cfg, one_chip)))
    return cfg, RunConfig(), ShardingCtx(one_chip), params


def _device_bytes(compiled):
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def _caches(cfg, mesh, slots, max_seq=2048):
    """The engine's cache of ``slots`` slots, as shapes on ``mesh``."""
    from repro.distribution.sharding import abstract_params
    from repro.models.model import cache_schema
    return _on(mesh, abstract_params(cache_schema(cfg, slots, max_seq)))


def _stack_copies(compiled, caches):
    """The copies of a whole cache stack in the compiled program, for every
    leaf shape of ``caches``."""
    import re
    import jax
    hlo = compiled.as_text()
    found = []
    for leaf in jax.tree.leaves(caches):
        dtype = {"bfloat16": "bf16", "float32": "f32"}[str(leaf.dtype)]
        shape = ",".join(map(str, leaf.shape))
        found += re.findall(rf"= {dtype}\[{shape}\]\S* copy\(", hlo)
    return found


def _install(cfg, mesh, caches):
    """The engine's install of a batch-1 prefill cache into one slot of
    ``caches``, cache donated and slot traced."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import install_slot
    one = _caches(cfg, mesh, 1)
    slot = _on(mesh, jax.ShapeDtypeStruct((), jnp.int32))
    return install_slot.lower(caches, one, slot).compile()


@pytest.fixture(scope="module")
def internlm2_caches(internlm2, one_chip):
    """The serve step's cache: 16 slots x 2048."""
    return _caches(internlm2[0], one_chip, 16)


@pytest.fixture(scope="module")
def internlm2_decode(internlm2, internlm2_caches, one_chip):
    """The serve step's decode, cache donated."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import forward_decode
    cfg, rcfg, shd, params = internlm2
    caches = internlm2_caches
    slots = jax.tree.leaves(caches)[0].shape[1]
    tokens, pos = _on(one_chip, (
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32)))

    def step(params, caches, tokens, pos):
        logits, caches = forward_decode(params, caches, tokens, pos, cfg,
                                        shd, rcfg)
        return jnp.argmax(logits, axis=-1), caches
    return jax.jit(step, donate_argnums=(1,)).lower(
        params, caches, tokens, pos).compile()


def test_internlm2_decode_fits_one_chip(internlm2_decode):
    assert _device_bytes(internlm2_decode) < V5E_HBM


def test_internlm2_decode_writes_the_cache_in_place(internlm2_decode,
                                                internlm2_caches):
    """The new K/V rows go into the donated cache in place: no temp the
    size of a cache stack, and no copy of a whole [24,16,2048,8,128] stack
    after the layer loop."""
    assert internlm2_decode.memory_analysis().temp_size_in_bytes < 64 << 20
    assert _stack_copies(internlm2_decode, internlm2_caches) == []


def test_internlm2_install_writes_the_slot_in_place(internlm2, one_chip,
                                                    internlm2_caches):
    """An admission's prefill cache goes into its slot of the donated
    cache: no temp the size of a cache stack, no copy of a whole stack."""
    compiled = _install(internlm2[0], one_chip, internlm2_caches)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert _stack_copies(compiled, internlm2_caches) == []


@pytest.fixture(scope="module")
def deepseek_v2(one_chip):
    """DeepSeek-V2 at one chip's share (9 layers, 10 of the router's 160
    experts, the benchmark's cut) and its cache of 102 slots x 2048."""
    import dataclasses
    from repro.configs import RunConfig, get_config
    from repro.distribution.sharding import ShardingCtx, abstract_params
    from repro.models.model import model_schema
    cfg = get_config("deepseek-v2-236b")
    cfg = dataclasses.replace(cfg, num_layers=9, moe=dataclasses.replace(
        cfg.moe, num_experts=10))
    shd, rcfg = ShardingCtx(one_chip), RunConfig()
    params = _on(one_chip, abstract_params(model_schema(cfg, one_chip)))
    return cfg, rcfg, shd, params, _caches(cfg, one_chip, 102)


@pytest.fixture(scope="module")
def deepseek_v2_decode(deepseek_v2, one_chip):
    """DeepSeek-V2's decode, cache donated, with the routed counts."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import forward_decode
    cfg, rcfg, shd, params, caches = deepseek_v2
    slots = jax.tree.leaves(caches)[0].shape[1]
    tokens, pos = _on(one_chip, (
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32)))

    def step(params, caches, tokens, pos):
        logits, caches, counts = forward_decode(
            params, caches, tokens, pos, cfg, shd, rcfg, return_counts=True)
        return jnp.argmax(logits, axis=-1), caches, counts
    return jax.jit(step, donate_argnums=(1,)).lower(
        params, caches, tokens, pos).compile()


def test_deepseek_v2_decode_writes_the_latent_cache_in_place(
        deepseek_v2_decode, deepseek_v2):
    """Both segments' latent rows go into the donated cache in place: the
    program fits the chip, has no temp the size of a cache stack, and
    copies no whole [1 or 8,102,2048,640] stack (with an unpadded 576-value
    row the chip's layout made it copy the stack in and out: 2.5 GB of
    temp)."""
    caches = deepseek_v2[-1]
    assert _device_bytes(deepseek_v2_decode) < V5E_HBM
    assert deepseek_v2_decode.memory_analysis().temp_size_in_bytes < 64 << 20
    assert _stack_copies(deepseek_v2_decode, caches) == []


def test_deepseek_v2_install_writes_the_slot_in_place(deepseek_v2, one_chip):
    """An admission's latent cache goes into its slot of the donated
    102-slot cache: no temp the size of a cache stack, no copy of a whole
    stack."""
    cfg, caches = deepseek_v2[0], deepseek_v2[-1]
    compiled = _install(cfg, one_chip, caches)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert _stack_copies(compiled, caches) == []


def test_internlm2_prefill_fits_one_chip(internlm2, one_chip):
    import jax
    import jax.numpy as jnp
    from repro.models.model import forward_prefill
    cfg, rcfg, shd, params = internlm2
    tokens = _on(one_chip, jax.ShapeDtypeStruct((1, 512), jnp.int32))
    compiled = jax.jit(
        lambda p, t: forward_prefill(p, t, cfg, shd, rcfg, max_seq=2048)
    ).lower(params, tokens).compile()
    assert _device_bytes(compiled) < V5E_HBM


@pytest.mark.parametrize("policy", ["xla", "ring", "hierarchical",
                                    "compressed", "shm-first"])
def test_nsm_psum_policies_on_pod_mesh(pod_mesh, policy):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import shard_map
    from repro.core import make_engine, nk_psum, use_engine
    axes = ("pod", "data")
    spec = P(axes, None)
    # 2 MiB per shard: above the ring policy's 1 MiB threshold
    x = jax.ShapeDtypeStruct((4096, 512), jnp.float32,
                             sharding=NamedSharding(pod_mesh, spec))
    eng = make_engine(pod_mesh, policy)

    def body(v):
        with use_engine(eng):
            return nk_psum(v, axes, gradient=True)
    hlo = jax.jit(shard_map(body, mesh=pod_mesh, in_specs=spec,
                            out_specs=spec)).lower(x).compile().as_text()
    if policy == "ring":
        assert "collective-permute" in hlo
    else:
        assert "all-reduce" in hlo
    assert eng.total_bytes() > 0


def _compile_kernel(one_chip, fn, *shapes):
    import jax
    from jax.sharding import SingleDeviceSharding
    dev = SingleDeviceSharding(one_chip.devices.flat[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_kernel(one_chip):
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention
    qkv = ((16, 2048, 128), jnp.bfloat16)
    hlo = _compile_kernel(one_chip, flash_attention, qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_decode_attention_kernel(one_chip):
    import jax.numpy as jnp
    from repro.kernels.decode_attention import decode_attention
    kv = ((8, 2048, 16, 128), jnp.bfloat16)
    hlo = _compile_kernel(one_chip, decode_attention,
                          ((8, 16, 128), jnp.bfloat16), kv, kv,
                          ((8,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_int8_codec_kernels(one_chip):
    import jax.numpy as jnp
    from repro.kernels.quant_comm import dequantize_int8, quantize_int8
    hlo = _compile_kernel(one_chip, quantize_int8,
                          ((4096, 4096), jnp.bfloat16))
    assert "tpu_custom_call" in hlo
    hlo = _compile_kernel(one_chip, dequantize_int8,
                          ((4096, 4096), jnp.int8), ((4096, 16), jnp.float32))
    assert "tpu_custom_call" in hlo
