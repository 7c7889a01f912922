"""Pallas TPU flash-decode: one query token per slot against one layer of a
segment's stacked K/V cache, read only up to each slot's position.

The cache stays the stack the decode scan carries, ``(L, B, S, KV, hd)``,
read as ``(L, B, S*KV, hd)``: a bitcast of the layout it has (the KV heads
of one position are one tile of rows), so a block of ``kv_block``
positions is one 2-D ``(kv_block*KV, hd)`` tile. The layer index and the
slots' positions ride in SMEM as scalar-prefetch operands; the stack stays
in HBM and no layer is sliced out of it.

The grid walks the slots. A slot at position ``p > 0`` loops over its
``p // kv_block + 1`` live blocks, copying each block of K and V into one
of two VMEM buffers while it works on the other; its last block starts the
copy of the next slot's first. A slot at position 0 reads nothing from the
kernel: its attention is its own row 0 of V, which the wrapper gathers.

Scores are taken of every query head against every row of a block, and
each query head is masked to the rows of its own KV head (GQA: query head
``h`` reads KV head ``h // g``): the spare MXU work is free in this
memory-bound regime. Scores and the online-softmax state (m, l, acc) stay
in VMEM and vregs in f32; no score reaches HBM.

VMEM at kv_block 256, KV 8, hd 128, 16 query heads, bf16: two buffers each
of K and V, 512 KiB a buffer; scores and weights (16, 2048) f32, 128 KiB
each.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG_INF = -2.0e30

# cache positions a block holds (a block of K or V is 512 KiB at KV 8,
# hd 128, bf16); of 128, 256 and 512 the fastest on a TPU v5e at
# internlm2-1.8b's decode shape, whether 3 of 16 slots are busy or all 16
# sit at the last position
KV_BLOCK = 256


def kv_block_for(seq: int, kv_block: int = KV_BLOCK) -> int:
    """The block a cache of ``seq`` positions is read in: ``kv_block``,
    or the whole sequence where it is shorter."""
    return min(kv_block, seq)


def blocks_read(pos, seq: int, kv_block: int = KV_BLOCK):
    """Cache blocks the kernel reads for slots at positions ``pos`` (none
    at position 0), and the blocks a read of every position would take;
    per layer, and for K (V reads as many)."""
    kvb = kv_block_for(seq, kv_block)
    return ((pos // kvb + 1) * (pos > 0)).sum(), len(pos) * (seq // kvb)


def _kernel(layer_ref, pos_ref, q_ref, k_hbm, v_hbm, row_kv_ref, col_kv_ref,
            col_t_ref, o_ref, k_buf, v_buf, sem, *, scale: float,
            kv_block: int):
    b, slots = pl.program_id(0), pl.num_programs(0)
    layer, pos = layer_ref[0], pos_ref[b]
    rows = k_buf.shape[1]

    def live_blocks(i):
        return jnp.where(pos_ref[i] > 0, pos_ref[i] // kv_block + 1, 0)

    def copies(i, j, buf):
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        return (pltpu.make_async_copy(k_hbm.at[layer, i, at], k_buf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, i, at], v_buf.at[buf],
                                      sem.at[1, buf]))

    n = live_blocks(b)
    # blocks go to the buffers in turn, across slots: this slot's first
    # takes the buffer after the last of every slot before it
    first = jax.lax.fori_loop(0, b, lambda i, c: c + live_blocks(i), 0) % 2
    fetched = jnp.logical_and(b > 0, live_blocks(jnp.maximum(b - 1, 0)) > 0)

    @pl.when(jnp.logical_and(n > 0, jnp.logical_not(fetched)))
    def _first():
        for c in copies(b, 0, first):
            c.start()

    q = q_ref[...]

    def block(j, carry):
        m_prev, l_prev, acc = carry
        buf = (first + j) % 2

        @pl.when(j + 1 < n)
        def _this_slot_next():
            for c in copies(b, j + 1, 1 - buf):
                c.start()

        nxt = jnp.minimum(b + 1, slots - 1)

        @pl.when((j + 1 == n) & (b + 1 < slots) & (live_blocks(nxt) > 0))
        def _next_slot_first():
            for c in copies(nxt, 0, 1 - buf):
                c.start()

        for c in copies(b, j, buf):
            c.wait()
        s = jax.lax.dot_general(
            q, k_buf[buf].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (H, kvb*KV)
        live = (row_kv_ref[...] == col_kv_ref[...]) \
            & (j * kv_block + col_t_ref[...] <= pos)
        s = jnp.where(live, s, NEG_INF)
        m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m)
        corr = jnp.exp(m_prev - m)
        return (m, l_prev * corr + jnp.sum(p, axis=1, keepdims=True),
                acc * corr + jax.lax.dot(
                    p.astype(q.dtype), v_buf[buf].astype(q.dtype),
                    preferred_element_type=jnp.float32))

    h, hd = q.shape
    _, l, acc = jax.lax.fori_loop(
        0, n, block, (jnp.full((h, 1), NEG_INF, jnp.float32),
                      jnp.ones((h, 1), jnp.float32),
                      jnp.zeros((h, hd), jnp.float32)))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def decode_attention(q, k, v, layer, pos, *, scale=None, kv_block=KV_BLOCK):
    """q: (B, H, hd), query head ``h`` reading KV head ``h // (H // KV)``;
    k, v: a segment's stacked cache (L, B, S, KV, hd), holding each slot's
    new row at ``pos``; layer: the layer's index in the stack; pos: (B,)
    each slot's position. Attends each slot to its positions ``0..pos``
    of layer ``layer``. Returns (B, H, hd) in q's dtype."""
    b, h, hd = q.shape
    n_layers, _, seq, kv, _ = k.shape
    if h % kv:
        raise ValueError(f"{h} query heads do not group over {kv} KV heads")
    kvb = kv_block_for(seq, kv_block)
    if seq % kvb:
        raise ValueError(f"a cache of {seq} positions is not a whole number "
                         f"of {kvb}-position blocks")
    rows = kvb * kv
    scale = scale or 1.0 / math.sqrt(hd)
    pos = pos.astype(jnp.int32)
    # which KV head each query row and each block row belongs to, and the
    # position of each block row within its block
    head_kv = jnp.arange(h, dtype=jnp.int32) // (h // kv)
    col = jnp.arange(rows, dtype=jnp.int32)[None, :]
    whole = lambda shape: pl.BlockSpec(shape, lambda *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, h, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            whole((h, 1)), whole((1, rows)), whole((1, rows)),
        ],
        out_specs=pl.BlockSpec((None, h, hd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, hd), k.dtype),
            pltpu.VMEM((2, rows, hd), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    flat = (n_layers, b, seq * kv, hd)
    o = pallas_call(
        functools.partial(_kernel, scale=scale, kv_block=kvb),
        grid_spec=grid_spec, name="flash_decode",
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        # the slots run in order: each prefetches the next one's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos, q, k.reshape(flat),
      v.reshape(flat), head_kv[:, None], col % kv, col // kv)
    # a slot at position 0 attends to its row 0 alone
    row0 = v[layer, :, 0][:, head_kv].astype(q.dtype)
    return jnp.where((pos == 0)[:, None, None], row0, o)
