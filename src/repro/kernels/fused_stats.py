"""Pallas TPU kernel: single-sweep row stats for the HiCS selection step.

The server-side selection path needs three per-client quantities from
the (N, C) bias-update matrix before the Gram kernel can run:

    entropy  Ĥ = H(softmax(Δb/T))     (Eq. 7 heterogeneity estimate)
    norm     |Δb|₂                     (Gram epilogue denominator)
    rms      sqrt(mean Δb²)            (normalized-estimator scale)

Computed separately (entropy kernel + ``jnp.linalg.norm`` + the pad
copy) that is three HBM sweeps over (N, C) — at LLM-head widths
(C up to 256k) the step is bandwidth-bound, so pass count ≈ wall time.
This kernel fuses all three into ONE streaming pass: the online-softmax
carry of ``hetero_entropy`` extended with a running sum of squares,

    (m, Z, S, Σx²)  per row, updated class-block by class-block,

emitting all three outputs in the last block's epilogue.  An optional
per-row scale multiplies rows before the tempered softmax (norm/RMS are
always of the raw rows) — that hook gives the ``normalize=True``
estimator (``core.hetero.estimate_entropy``) a kernel path: sweep once
for RMS, once more with scale = 1/RMS, instead of no Pallas route at
all.

Grid: (row blocks, class blocks); the class axis is minor/sequential so
the VMEM scratch carries state row-block by row-block, exactly like
``hetero_entropy``.  Rows pad to 8, classes block at 512 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fused_stats_kernel(x_ref, scale_ref, ent_ref, norm_ref, rms_ref,
                        m_ref, z_ref, s_ref, ss_ref, *, c_total, block_c):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        z_ref[...] = jnp.zeros_like(z_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    x = x_ref[...].astype(jnp.float32)                      # (bn, bc)
    u = x * scale_ref[...]      # scale carries 1/T (and 1/RMS if used)
    # mask the tail of the last class block
    col = ci * block_c + jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    valid = col < c_total
    u = jnp.where(valid, u, NEG_INF)

    m_prev = m_ref[...]                                     # (bn, 1)
    m_blk = jnp.max(u, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)
    e = jnp.where(valid, jnp.exp(u - m_new), 0.0)
    z_blk = jnp.sum(e, axis=-1, keepdims=True)
    s_blk = jnp.sum(e * jnp.where(valid, u - m_new, 0.0), axis=-1,
                    keepdims=True)
    z_prev = z_ref[...]
    s_prev = s_ref[...]
    z_new = z_prev * alpha + z_blk
    s_new = (s_prev + (m_prev - m_new) * z_prev) * alpha + s_blk
    # sum of squares needs no column mask: padded tail entries are zero
    ss_new = ss_ref[...] + jnp.sum(
        jnp.where(valid, x * x, 0.0), axis=-1, keepdims=True)
    m_ref[...] = m_new
    z_ref[...] = z_new
    s_ref[...] = s_new
    ss_ref[...] = ss_new

    @pl.when(ci == nc - 1)
    def _epilogue():
        ent_ref[...] = jnp.log(z_new) - s_new / z_new
        norm_ref[...] = jnp.sqrt(ss_new)
        rms_ref[...] = jnp.sqrt(ss_new / c_total)


def _fused_stats_padded(x: jnp.ndarray, scale_col: jnp.ndarray,
                        c_total: int, block_n: int, block_c: int,
                        interpret: bool):
    """Run the kernel on an already padded/aligned (n_pad, c_pad) buffer.

    Shared by :func:`fused_stats_pallas` (which pads) and the fused
    selection step in ``ops.py`` (which pads ONCE for both this kernel
    and the Gram kernel).  ``scale_col`` (n_pad, 1) carries 1/T — and
    1/RMS on the normalized second pass.  Returns (ent, norm, rms),
    each (n_pad,).
    """
    n_pad, c_pad = x.shape
    grid = (n_pad // block_n, c_pad // block_c)
    ent, norm, rms = pl.pallas_call(
        functools.partial(_fused_stats_kernel,
                          c_total=c_total, block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            # (m, z, s, Σx²) running stats in VMEM, one lane per row
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_row_stats",
    )(x, scale_col)
    return ent[:, 0], norm[:, 0], rms[:, 0]


@functools.partial(jax.jit,
                   static_argnames=("temperature", "block_n", "block_c",
                                    "interpret"))
def fused_stats_pallas(updates: jnp.ndarray, temperature: float,
                       row_scale: jnp.ndarray | None = None,
                       block_n: int = 8, block_c: int = 512,
                       interpret: bool = True):
    """(N, C) -> (entropy, l2 norm, RMS), each (N,) f32, in one sweep.

    ``row_scale`` (N,) optionally multiplies each row before the
    tempered softmax; norm/RMS always describe the raw rows.
    interpret=True on CPU (the TPU is the compile target; this
    container validates in interpret mode).
    """
    n, c = updates.shape
    n_pad = -(-n // block_n) * block_n
    c_pad = -(-c // block_c) * block_c
    x = jnp.pad(updates, ((0, n_pad - n), (0, c_pad - c)))
    # fold the temperature into the per-row scale: u = x·s/T
    scale = (jnp.full((n,), 1.0 / temperature, jnp.float32)
             if row_scale is None
             else row_scale.astype(jnp.float32) / temperature)
    scale_col = jnp.pad(scale, (0, n_pad - n),
                        constant_values=1.0)[:, None]
    ent, norm, rms = _fused_stats_padded(x, scale_col, c, block_n,
                                         block_c, interpret)
    return ent[:n], norm[:n], rms[:n]
