"""Pallas TPU kernel: GQA flash-decode (one query token vs. blocked KV).

The serving hot loop for decode_32k / long_500k: one new token attends
to a KV cache of up to 512k positions.  Per (batch, kv-head) the kernel
streams the cache through VMEM in S-blocks with online softmax:

    m' = max(m, max(logits_blk));  l' = l·e^{m−m'} + Σe^{logits−m'}
    o' = o·e^{m−m'} + e^{logits−m'} · V_blk

All G = H/KV query heads of one KV group ride together so each K/V
block is read from HBM exactly once per group (GQA's whole point); the
(G, dh) accumulator and (G, 1) stats stay in VMEM scratch across the
sequence grid axis.  Positions ≥ `length` (ragged cache) are masked.

Grid: (B, KV, S/BS); S minor/sequential.  Block shapes: (G, dh) query
tile, (BS, dh) K/V tiles — dh ∈ {64, 128, 256} are all lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, block_s):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)         # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)         # (BS, dh)
    v = v_ref[0, 0].astype(jnp.float32)         # (BS, dh)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (G, BS)
    pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = pos < len_ref[0]
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]                          # (G, 1)
    m_blk = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)    # (G, BS)
    l_new = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_new = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (G, dh)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(si == ns - 1)
    def _epilogue():
        o_ref[0, 0] = acc_new / jnp.maximum(l_new, 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_s", "interpret"))
def decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            length, scale: float | None = None,
                            block_s: int = 512,
                            interpret: bool = True) -> jnp.ndarray:
    """q: (B, H, dh); k/v: (B, S, KV, dh); length: () or (B,) valid len.

    Returns (B, H, dh) f32.  H % KV == 0 (GQA).
    """
    B, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    g = H // KV
    scale = (dh ** -0.5) if scale is None else scale
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    bs = min(block_s, S)
    s_pad = -(-S // bs) * bs
    kp = jnp.pad(k, ((0, 0), (0, s_pad - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, s_pad - S), (0, 0), (0, 0)))
    # (B, KV, G, dh) query; (B, KV, S, dh) cache — kv-head major for tiling
    qg = q.reshape(B, KV, g, dh)
    kt = kp.transpose(0, 2, 1, 3)
    vt = vp.transpose(0, 2, 1, 3)
    grid = (B, KV, s_pad // bs)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_s=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, s: (b,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, dh), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, dh), lambda b, h, s: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
        interpret=interpret,
        name="gqa_decode_attention",
    )(lengths, qg, kt, vt)
    return out.reshape(B, H, dh)
