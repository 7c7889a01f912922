"""Per-kernel validation vs the pure-jnp oracles (the Pallas interpreter on
the CPU), with shape/dtype sweeps and hypothesis property checks."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention as flash_decode
from repro.models.attention import decode_attention, q_to_kv_map

KS = jax.random.split(jax.random.PRNGKey(0), 8)


@pytest.mark.parametrize("shape,causal,window,dtype", [
    ((2, 4, 256, 64), True, 0, jnp.float32),
    ((1, 2, 200, 128), True, 64, jnp.float32),
    ((2, 2, 128, 64), False, 0, jnp.float32),
    ((1, 3, 160, 64), True, 32, jnp.bfloat16),
])
def test_flash_attention_vs_ref(shape, causal, window, dtype):
    b, h, s, d = shape
    q = jax.random.normal(KS[0], shape, dtype)
    k = jax.random.normal(KS[1], shape, dtype)
    v = jax.random.normal(KS[2], shape, dtype)
    o1 = ops.mha_forward(q, k, v, causal=causal, window=window,
                         impl="pallas", q_block=64, kv_block=64)
    o2 = ops.mha_forward(q, k, v, causal=causal, window=window, impl="ref")
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_decode_attention_vs_ref(g, dtype):
    """The flash-decode kernel on a stacked (L, B, S, KV, hd) cache, at a
    traced layer index, against the jnp decode attention on that layer
    alone, for ragged positions: idle slots at 0 (one between busy slots,
    which hand their first blocks on to each other), the last position of
    the first block, the first of the second, the last of the cache, and
    1. Every layer holds other data, so reading the wrong one shows."""
    n_layers, kv, hd, kv_block = 3, 2, 64, 16
    s = 4 * kv_block
    h = kv * g
    pos = jnp.array([0, kv_block - 1, 0, kv_block, s - 1, 1], jnp.int32)
    b = pos.shape[0]
    q = jax.random.normal(KS[3], (b, h, hd), dtype)
    k = jax.random.normal(KS[4], (n_layers, b, s, kv, hd), dtype)
    v = jax.random.normal(KS[5], (n_layers, b, s, kv, hd), dtype)
    layer = jnp.int32(1)
    got = jax.jit(functools.partial(flash_decode, kv_block=kv_block))(
        q, k, v, layer, pos)
    want = decode_attention(q[:, None], k[layer], v[layer], pos,
                            kv_map=q_to_kv_map(h, h, kv))[:, 0]
    assert got.shape == (b, h, hd) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_decode_lse_combine_across_shards():
    """Sharded-cache partials combine to the unsharded result (the
    context-parallel decode contract)."""
    b, h, t, d = 2, 4, 256, 32
    q = jax.random.normal(KS[0], (b, h, d), jnp.float32)
    k = jax.random.normal(KS[1], (b, t, h, d), jnp.float32)
    v = jax.random.normal(KS[2], (b, t, h, d), jnp.float32)
    pos = jnp.array([200, 255])
    o_full, _, _ = ref.decode_attention_ref(q, k, v, pos)
    # two shards of the cache, each with local positions
    half = t // 2
    o0, m0, l0 = ref.decode_attention_ref(q, k[:, :half], v[:, :half], pos)
    o1, m1, l1 = ref.decode_attention_ref(
        q, k[:, half:], v[:, half:], pos - half)
    m = jnp.maximum(m0, m1)
    w0 = jnp.exp(m0 - m) * l0
    w1 = jnp.exp(m1 - m) * l1
    o = (o0 * w0[..., None] + o1 * w1[..., None]) / (w0 + w1)[..., None]
    np.testing.assert_allclose(o, o_full, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H,head_block", [(16, 8), (8, 8), (32, 16)])
def test_ssd_chunk_vs_ref(H, head_block):
    nb, nc, Q, P, N = 2, 3, 64, 32, 64
    xdt = jax.random.normal(KS[6], (nb, nc, Q, H, P), jnp.float32) * 0.1
    dA = -jnp.abs(jax.random.normal(KS[7], (nb, nc, Q, H), jnp.float32)) * 0.1
    B = jax.random.normal(KS[0], (nb, nc, Q, N), jnp.float32) * 0.3
    C = jax.random.normal(KS[1], (nb, nc, Q, N), jnp.float32) * 0.3
    y1, st1, dec1 = ops.ssd_intra_chunk(xdt, dA, B, C, impl="pallas",
                                        head_block=head_block)
    y2, st2, dec2 = ops.ssd_intra_chunk(xdt, dA, B, C, impl="ref")
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(st1, st2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(dec1, dec2, rtol=1e-5, atol=1e-5)


@given(r=st.integers(1, 64), cb=st.integers(1, 8),
       scale=st.floats(0.01, 100.0))
@settings(max_examples=25, deadline=None)
def test_quantization_error_bound(r, cb, scale):
    """Property: blockwise int8 error <= scale/2 elementwise (no clipping
    can occur since scale = absmax/127)."""
    c = cb * 128
    x = jax.random.normal(jax.random.PRNGKey(r), (r, c), jnp.float32) * scale
    q8, s = ops.quantize(x, block=128, impl="pallas")
    xr = np.asarray(ops.dequantize(q8, s, block=128))
    x = np.asarray(x)
    err = np.abs(xr - x)
    # the f32 roundings of x / scale and q * scale add half an ulp each, of
    # |x| and |xr|: a fixed 1e-6 is below one ulp once |x| passes 16
    bound = (np.repeat(np.asarray(s), 128, axis=1) * 0.5
             + np.spacing(np.maximum(np.abs(x), np.abs(xr))))
    assert (err <= bound).all()


def test_quantize_pallas_matches_ref():
    x = jax.random.normal(KS[2], (100, 512), jnp.float32) * 3
    q8, s = ops.quantize(x, block=128, impl="pallas")
    q8r, sr = ops.quantize(x, block=128, impl="ref")
    np.testing.assert_array_equal(np.asarray(q8), np.asarray(q8r))
    np.testing.assert_allclose(s, sr, rtol=1e-6)
