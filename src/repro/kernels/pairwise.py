"""Pallas TPU kernel: Eq. 9 pairwise client distance.

    D[u, k] = arccos( <Δb_u, Δb_k> / (|Δb_u||Δb_k|) ) + λ |Ĥ_u − Ĥ_k|

Inputs are the (N, C) bias-update matrix (C = classes/vocab, up to
256k) and a per-row stats vector (N, 2) = [L2 norm, Ĥ] — both produced
in ONE streaming pass by ``fused_stats``.  The kernel tiles the Gram
product X Xᵀ for the MXU — (BN, BC) × (BC, BN) partial products
accumulated in a VMEM f32 scratch over the C grid axis — and writes the
clipped cosine on the last C block, so the (N, N) result is written to
HBM exactly once.

The angular tail (arccos, zeroing the true diagonal, +λ|ΔĤ|) runs in
XLA in the jitted wrapper (:func:`gram_tail`): Mosaic has no lowering
for ``acos``/``atan``, and the tail is O(N²) elementwise work next to
the O(N²·C) Gram sweep.

``hics_selection_step_pallas`` is the end-to-end fused selection step:
it pads (N, C) ONCE, runs the fused stats sweep, and feeds the outputs
straight into this Gram kernel inside a single jit — no host round
trip, and optionally with the Gram operands cast to bf16 (f32
accumulation stays) for 2× Gram bandwidth.

Grid: (row tiles i, col tiles j, C blocks); C is minor/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_stats import _fused_stats_padded


def _gram_tile(a_ref, b_ref) -> jnp.ndarray:
    """f32 partial Gram a·bᵀ of two (rows, BC) VMEM tiles.  f32 operands
    ask for Mosaic's fp32 contract precision: its default is one bf16
    pass, which put the C=10 arccos ~5e-3 off the f32 oracle on a v5e.
    bf16 operands (``gram_in_bf16``) keep the single native pass."""
    a, b = a_ref[...], b_ref[...]
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)


def _pairwise_kernel(x_ref, xt_ref, stats_ref, statsT_ref, o_ref,
                     acc_ref, *, eps):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _gram_tile(x_ref, xt_ref)    # rows × cols tiles

    @pl.when(ci == nc - 1)
    def _epilogue():
        # stats lanes: [:, 0] = L2 norm, [:, 1] = entropy
        nr = stats_ref[..., 0:1].astype(jnp.float32)      # (BN, 1)
        ncol = statsT_ref[..., 0:1].astype(jnp.float32)   # (BN, 1)
        denom = jnp.maximum(nr, eps) * jnp.maximum(ncol, eps).T
        o_ref[...] = jnp.clip(acc_ref[...] / denom, -1.0 + 1e-7,
                              1.0 - 1e-7)


def gram_tail(g: jnp.ndarray, row_ids: jnp.ndarray, h_rows: jnp.ndarray,
              h_cols: jnp.ndarray, lam: float,
              epilogue: str = "arccos") -> jnp.ndarray:
    """XLA tail of the Gram kernels: (R, N) kernel output -> distance.

    ``g`` is the clipped cosine ("arccos"/"cosine") or the L2 distance
    ("l2").  The cosine family takes the arccos; every epilogue zeroes
    the true diagonal (strip row ``row_ids[r]`` against column index
    r'), and "arccos" adds Eq. 9's λ|Ĥ_row − Ĥ_col|.
    """
    d = g if epilogue == "l2" else jnp.arccos(g)
    d = jnp.where(row_ids[:, None] == jnp.arange(g.shape[1])[None, :],
                  0.0, d)
    if epilogue == "arccos":
        d = d + lam * jnp.abs(h_rows[:, None] - h_cols[None, :])
    return d


def _pairwise_padded(x: jnp.ndarray, stats: jnp.ndarray, eps: float,
                     bn: int, block_c: int,
                     interpret: bool) -> jnp.ndarray:
    """Gram/cosine kernel on an already padded (n_pad, c_pad) buffer.

    ``stats`` is (n_pad, 2) = [norm, entropy]; padded rows must carry a
    nonzero norm.  The same buffer feeds the row and column tiles (two
    operand slots, one HBM allocation — no copy is made).
    """
    n_pad = x.shape[0]
    c_pad = x.shape[1]
    grid = (n_pad // bn, n_pad // bn, c_pad // block_c)
    return pl.pallas_call(
        functools.partial(_pairwise_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, block_c), lambda i, j, k: (i, k)),  # rows
            pl.BlockSpec((bn, block_c), lambda i, j, k: (j, k)),  # cols
            pl.BlockSpec((bn, 2), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bn, 2), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        interpret=interpret,
        name="pairwise_gram",
    )(x, x, stats, stats)


def _gram_blocks(n: int, c: int, block_n: int, block_c: int):
    """Padded sizes aligned for the Gram tiling: (bn, n_pad, c_pad)."""
    bn = min(block_n, max(8, -(-n // 8) * 8))
    return bn, -(-n // bn) * bn, -(-c // block_c) * block_c


@functools.partial(jax.jit,
                   static_argnames=("lam", "block_n", "block_c",
                                    "gram_in_bf16", "interpret"))
def pairwise_distance_pallas(updates: jnp.ndarray, norms: jnp.ndarray,
                             entropies: jnp.ndarray, lam: float = 10.0,
                             block_n: int = 128, block_c: int = 512,
                             gram_in_bf16: bool = False,
                             interpret: bool = True) -> jnp.ndarray:
    """(N, C), (N,), (N,) -> (N, N) Eq. 9 distances (f32)."""
    n, c = updates.shape
    bn, n_pad, c_pad = _gram_blocks(n, c, block_n, block_c)
    x = jnp.pad(updates, ((0, n_pad - n), (0, c_pad - c)))
    if gram_in_bf16:
        x = x.astype(jnp.bfloat16)
    # pad norms with 1s so padded rows don't divide by 0
    nr = jnp.pad(norms.astype(jnp.float32), (0, n_pad - n),
                 constant_values=1.0)
    h = jnp.pad(entropies.astype(jnp.float32), (0, n_pad - n))
    stats = jnp.stack([nr, h], axis=-1)                  # (n_pad, 2)
    cos = _pairwise_padded(x, stats, 1e-8, bn, block_c, interpret)
    h = h[:n]
    return gram_tail(cos[:n, :n], jnp.arange(n), h, h, lam)


@functools.partial(jax.jit,
                   static_argnames=("temperature", "lam", "normalize",
                                    "block_n", "block_c", "gram_in_bf16",
                                    "interpret"))
def hics_selection_step_pallas(updates: jnp.ndarray, temperature: float,
                               lam: float = 10.0, normalize: bool = False,
                               block_n: int = 128, block_c: int = 512,
                               gram_in_bf16: bool = False,
                               interpret: bool = True):
    """Fused HiCS selection step: (N, C) -> (Ĥ (N,), Eq. 9 D (N, N)).

    One pad, one pre-Gram HBM sweep (the fused stats kernel), then the
    Gram kernel on the same padded buffer, then the XLA arccos tail —
    all inside one jit.
    ``normalize=True`` adds a second stats sweep with rows scaled by
    1/RMS (the magnitude-invariant estimator); the unfused baseline had
    no kernel path for it at all.  ``gram_in_bf16`` halves Gram operand
    bandwidth while keeping f32 accumulation.
    """
    n, c = updates.shape
    bn, n_pad, c_pad = _gram_blocks(n, c, block_n, block_c)
    x = jnp.pad(updates, ((0, n_pad - n), (0, c_pad - c)))
    inv_t = jnp.full((n_pad, 1), 1.0 / temperature, jnp.float32)
    ent, norm, rms = _fused_stats_padded(x, inv_t, c, 8, block_c,
                                         interpret)
    if normalize:
        scale = 1.0 / (jnp.clip(rms, 1e-12, None)[:, None] * temperature)
        ent, _, _ = _fused_stats_padded(x, scale, c, 8, block_c,
                                        interpret)
    # padded rows have zero norm; give them 1 so the epilogue never
    # divides by eps² (their rows/cols are sliced away below)
    live = jnp.arange(n_pad) < n
    stats = jnp.stack([jnp.where(live, norm, 1.0), ent], axis=-1)
    xg = x.astype(jnp.bfloat16) if gram_in_bf16 else x
    cos = _pairwise_padded(xg, stats, 1e-8, bn, block_c, interpret)
    ent = ent[:n]
    return ent, gram_tail(cos[:n, :n], jnp.arange(n), ent, ent, lam)
