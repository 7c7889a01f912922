"""Blockwise attention vs the naive oracle: causal, windows, padding,
GQA maps, inert padded heads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (
    blockwise_attention, decode_attention, head_mask, naive_attention,
    q_to_kv_map,
)

KS = jax.random.split(jax.random.PRNGKey(1), 4)


@pytest.mark.parametrize("s,t,qb,kvb,causal,window", [
    (128, 128, 32, 32, True, 0),
    (100, 100, 32, 32, True, 0),       # padding path
    (128, 128, 32, 32, True, 48),      # window
    (64, 192, 32, 32, False, 0),       # cross-attention shape
    (96, 96, 128, 128, True, 33),      # blocks larger than seq
])
def test_blockwise_matches_naive(s, t, qb, kvb, causal, window):
    b, h, kv, d = 2, 6, 3, 32
    q = jax.random.normal(KS[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(KS[1], (b, t, kv, d), jnp.float32)
    v = jax.random.normal(KS[2], (b, t, kv, d), jnp.float32)
    kv_map = q_to_kv_map(h, h, kv)
    o1 = blockwise_attention(q, k, v, kv_map=kv_map, causal=causal,
                             window=window, q_block=qb, kv_block=kvb)
    o2 = naive_attention(q, k, v, kv_map=kv_map, causal=causal, window=window)
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)


def test_padded_heads_are_inert():
    """Masked padded heads contribute 0 to outputs AND receive 0 grads."""
    b, s, h, hp, kv, d = 1, 32, 3, 4, 1, 16
    q = jax.random.normal(KS[0], (b, s, hp, d), jnp.float32)
    k = jax.random.normal(KS[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(KS[2], (b, s, kv, d), jnp.float32)
    wo = jax.random.normal(KS[3], (hp, d, 8), jnp.float32)
    mask = head_mask(h, hp, jnp.float32)

    def out(q, wo):
        o = blockwise_attention(q, k, v, kv_map=q_to_kv_map(h, hp, kv),
                                q_block=16, kv_block=16)
        o = o * mask[None, None, :, None]
        return jnp.einsum("bshk,hkd->bsd", o, wo)

    y = out(q, wo)
    # changing padded-head inputs/weights must not change the output
    q2 = q.at[:, :, h:].set(123.0)
    wo2 = wo.at[h:].set(-7.0)
    np.testing.assert_allclose(y, out(q2, wo), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, out(q, wo2), rtol=1e-6, atol=1e-6)
    # gradients into padded slices are exactly zero
    gq, gwo = jax.grad(lambda q_, w_: jnp.sum(out(q_, w_) ** 2),
                       argnums=(0, 1))(q, wo)
    assert float(jnp.abs(gq[:, :, h:]).max()) == 0.0
    assert float(jnp.abs(gwo[h:]).max()) == 0.0


def test_ring_buffer_decode_window():
    """Window-cache ring layout attends exactly the last `window` tokens."""
    b, kv, d, w = 1, 2, 16, 8
    hq = 2
    kv_map = q_to_kv_map(hq, hq, kv)
    q = jax.random.normal(KS[0], (b, 1, hq, d), jnp.float32)
    # linear cache of 32 tokens, pos = 20
    t = 32
    k = jax.random.normal(KS[1], (b, t, hq, d), jnp.float32)
    v = jax.random.normal(KS[2], (b, t, hq, d), jnp.float32)
    pos = jnp.array([20])
    o_lin = decode_attention(q, k, v, pos, kv_map=kv_map, window=w)
    # ring layout: slot j holds token pos - ((pos - j) % w)
    slots = (20 - ((20 - jnp.arange(w)) % w))
    kr = k[:, slots]
    vr = v[:, slots]
    kv_pos = jnp.broadcast_to(slots[None], (b, w))
    o_ring = decode_attention(q, kr, vr, pos, kv_map=kv_map, window=w,
                              kv_pos=kv_pos)
    np.testing.assert_allclose(o_ring, o_lin, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "flash", ""])
def test_unimplemented_attention_impl_raises(impl):
    """An impl name the model does not implement must not fall through to
    the chunked path."""
    from repro.configs import RunConfig
    with pytest.raises(ValueError, match="attention_impl"):
        RunConfig(attention_impl=impl)
    assert RunConfig(attention_impl="naive").attention_impl == "naive"
