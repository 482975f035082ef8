"""Pallas TPU kernel: fused temperature-softmax entropy over class blocks.

Server-side HiCS-FL computes Ĥ(D^(k)) = H(softmax(Δb^(k)/T)) for N
clients at once: input (N, C) with C = number of classes = LLM vocab
(up to 256,206 for seamless).  At that width a naive softmax+entropy
materializes three (N, C) f32 temporaries in HBM; this kernel streams C
through VMEM in blocks with the flash-attention online-softmax trick
adapted to the entropy epilogue

    H = lnZ − S/Z,   Z = Σ e^{u−m},  S = Σ e^{u−m}(u−m),  u = v/T

carrying (m, Z, S) per row across class blocks and rescaling on each
new running max:  Z' = Z·e^{m−m'} + Z_b,  S' = (S + (m−m')Z)·e^{m−m'} + S_b.

Grid: (row blocks, class blocks); the class axis is the minor
(sequential) grid dimension, so the scratch carries state row-block by
row-block.  Block shapes are MXU/VPU aligned: rows padded to 8, classes
blocked at 512 lanes (multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _entropy_kernel(x_ref, o_ref, m_ref, z_ref, s_ref, *, temperature,
                    c_total, block_c):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        z_ref[...] = jnp.zeros_like(z_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    u = x_ref[...].astype(jnp.float32) / temperature       # (bn, bc)
    # mask the tail of the last class block
    col = ci * block_c + jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    valid = col < c_total
    u = jnp.where(valid, u, NEG_INF)

    m_prev = m_ref[...]                                     # (bn, 1)
    m_blk = jnp.max(u, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)                         # rescale factor
    e = jnp.where(valid, jnp.exp(u - m_new), 0.0)
    z_blk = jnp.sum(e, axis=-1, keepdims=True)
    s_blk = jnp.sum(e * jnp.where(valid, u - m_new, 0.0), axis=-1,
                    keepdims=True)
    z_prev = z_ref[...]
    s_prev = s_ref[...]
    z_new = z_prev * alpha + z_blk
    s_new = (s_prev + (m_prev - m_new) * z_prev) * alpha + s_blk
    m_ref[...] = m_new
    z_ref[...] = z_new
    s_ref[...] = s_new

    @pl.when(ci == nc - 1)
    def _epilogue():
        o_ref[...] = jnp.log(z_new) - s_new / z_new


@functools.partial(jax.jit,
                   static_argnames=("temperature", "block_n", "block_c",
                                    "interpret"))
def entropy_pallas(updates: jnp.ndarray, temperature: float,
                   block_n: int = 8, block_c: int = 512,
                   interpret: bool = True) -> jnp.ndarray:
    """(N, C) -> (N,) f32 entropies.  interpret=True on CPU (the TPU is
    the compile target; this container validates in interpret mode)."""
    n, c = updates.shape
    n_pad = -(-n // block_n) * block_n
    c_pad = -(-c // block_c) * block_c
    x = jnp.pad(updates, ((0, n_pad - n), (0, c_pad - c)))
    grid = (n_pad // block_n, c_pad // block_c)
    out = pl.pallas_call(
        functools.partial(_entropy_kernel, temperature=temperature,
                          c_total=c, block_c=block_c),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, block_c),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        scratch_shapes=[
            # (m, z, s) running stats in VMEM, one lane per row
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="estimate_entropies",
    )(x)
    return out[:n, 0]
