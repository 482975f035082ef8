"""Pallas TPU kernel: K-row incremental update of a cached distance.

HiCS-FL's Algorithm 1 replaces only the K participating clients' Δb
rows each round, so N−K rows of the Gram/arccos distance matrix carry
over round-to-round.  This module is the device half of that caching
scheme: instead of the full (N, N) Gram product — O(N²·C) HBM traffic
and MXU work per round — it recomputes just the K×N strip

    D[u, j] = arccos( <Δb_u, Δb_j> / (|Δb_u||Δb_j|) ) + λ |Ĥ_u − Ĥ_j|

for the refreshed rows u ∈ ids, O(K·N·C), and scatters it back into
the cached matrix (rows AND columns — dot products are symmetric, so
the scatter keeps the cache exactly symmetric).

The Gram product is metric-agnostic, so the distance is one of three
EPILOGUES: "arccos" (Eq. 9, HiCS), "cosine" (Clustered Sampling's
angular distance over full updates) and "l2" (DivFL's Euclidean
distance, rebuilt from the cached norms via |a−b|² = |a|² + |b|² −
2⟨a, b⟩).  That one switch lets the full-update baselines ride the SAME
cached K-row path HiCS uses — ``cached_feature_step_pallas`` below —
which is what puts DivFL/CS on the scanned round loop at O(K·N·F) per
round.

The strip kernel reuses the Gram tiling of ``kernels/pairwise``: (BK,
BC) × (BN, BC) partial products accumulated in a VMEM f32 scratch over
the sequential C axis.  On the last C block it writes the clipped
cosine (or, for "l2", the distance) so the strip reaches HBM exactly
once; the arccos, the true-diagonal zeroing (by the refreshed rows'
GLOBAL ids) and +λ|ΔĤ| run in XLA on the (K, N) strip
(``pairwise.gram_tail``) — Mosaic cannot lower ``acos``.
``gram_in_bf16`` casts both Gram operands to bf16 (f32 accumulation
stays) for 2× operand bandwidth, exactly like the full kernel.

``cached_selection_step_pallas`` is the end-to-end incremental
selection step: gather the K rows, one fused-stats sweep over (K, C)
(entropy + L2 norm, plus the RMS-normalized second sweep when
``normalize=True``), the strip kernel, and the row/col scatter — all
inside one jit.  Grid: (K tiles, N tiles, C blocks); C minor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.fused_stats import _fused_stats_padded
from repro.kernels.pairwise import _gram_blocks, _gram_tile, gram_tail


#: strip epilogues: how the K×N Gram product becomes a distance.
#: "arccos" is Eq. 9 (HiCS); "cosine" is the angular distance alone
#: (Clustered Sampling); "l2" is Euclidean distance from the cached
#: norms (DivFL).  The kernel stops at the clipped cosine (or the L2
#: distance); the rest is ``pairwise.gram_tail`` in XLA.
EPILOGUES = ("arccos", "cosine", "l2")

_BK = 8   # strip row-tile: K is small (a cohort), one VPU sublane tile


def _gram_row_kernel(rows_ref, x_ref, stats_r_ref, stats_c_ref, o_ref,
                     acc_ref, *, eps, l2):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (BK, BC) refreshed rows × (BN, BC) all-clients tile
    acc_ref[...] += _gram_tile(rows_ref, x_ref)

    @pl.when(ci == nc - 1)
    def _epilogue():
        # stats lanes: [:, 0] = L2 norm, [:, 1] = entropy
        nr = stats_r_ref[..., 0:1].astype(jnp.float32)    # (BK, 1)
        ncol = stats_c_ref[..., 0:1].astype(jnp.float32)  # (BN, 1)
        if l2:
            # √(|a|² + |b|² − 2⟨a, b⟩) from the cached norms; the clip
            # absorbs the fp cancellation of near-identical rows
            o_ref[...] = jnp.sqrt(jnp.clip(
                nr * nr + (ncol * ncol).T - 2.0 * acc_ref[...], 0.0,
                None))
        else:                                 # cosine family
            denom = jnp.maximum(nr, eps) * jnp.maximum(ncol, eps).T
            o_ref[...] = jnp.clip(acc_ref[...] / denom, -1.0 + 1e-7,
                                  1.0 - 1e-7)


def _gram_strip(x_pad: jnp.ndarray, stats: jnp.ndarray, ids: jnp.ndarray,
                n: int, lam: float, bn: int, block_c: int,
                gram_in_bf16: bool, interpret: bool,
                epilogue: str = "arccos") -> jnp.ndarray:
    """(K, N) distance strip from the padded (n_pad, c_pad) buffer and
    the CURRENT (N, 2) stats: the strip kernel, then ``gram_tail``.
    Shared by every entry point so their invariants cannot drift:
    padded stats lanes carry norm 1 (never divide by eps²) and the bf16
    cast happens AFTER any f32 consumer of the buffers."""
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}; expected one "
                         f"of {EPILOGUES}")
    n_pad, c_pad = x_pad.shape
    k = ids.shape[0]
    k_pad = max(_BK, -(-k // _BK) * _BK)
    rows = jnp.pad(x_pad[ids], ((0, k_pad - k), (0, 0)))
    live = jnp.arange(n_pad) < n
    stats_all = jnp.stack(
        [jnp.where(live, jnp.pad(stats[:, 0], (0, n_pad - n)), 1.0),
         jnp.pad(stats[:, 1], (0, n_pad - n))], axis=-1)
    stats_rows = jnp.pad(stats[ids], ((0, k_pad - k), (0, 0)),
                         constant_values=1.0)
    if gram_in_bf16:
        x_pad = x_pad.astype(jnp.bfloat16)
        rows = rows.astype(jnp.bfloat16)
    g = pl.pallas_call(
        functools.partial(_gram_row_kernel, eps=1e-8,
                          l2=epilogue == "l2"),
        grid=(k_pad // _BK, n_pad // bn, c_pad // block_c),
        in_specs=[
            pl.BlockSpec((_BK, block_c), lambda i, j, k: (i, k)),  # rows
            pl.BlockSpec((bn, block_c), lambda i, j, k: (j, k)),   # cols
            pl.BlockSpec((_BK, 2), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bn, 2), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((_BK, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_BK, bn), jnp.float32)],
        interpret=interpret,
        name="gram_strip",
    )(rows, x_pad, stats_rows, stats_all)
    return gram_tail(g[:k, :n], ids, stats[ids, 1], stats[:, 1], lam,
                     epilogue)


@functools.partial(jax.jit,
                   static_argnames=("lam", "block_n", "block_c",
                                    "gram_in_bf16", "interpret",
                                    "epilogue"))
def gram_row_update_pallas(updates: jnp.ndarray, stats: jnp.ndarray,
                           ids: jnp.ndarray, lam: float = 10.0,
                           block_n: int = 128, block_c: int = 512,
                           gram_in_bf16: bool = False,
                           interpret: bool = True,
                           epilogue: str = "arccos") -> jnp.ndarray:
    """(N, C), (N, 2) stats, (K,) ids -> (K, N) distance strip.

    ``epilogue`` picks the distance (see :data:`EPILOGUES`): "arccos"
    is the Eq. 9 strip, "cosine"/"l2" serve the full-update baselines.
    ``stats`` must already hold the CURRENT [norm, Ĥ] of every row
    (including the refreshed ones); this is just the tiled strip
    product + epilogue.  ``cached_selection_step_pallas`` wraps it with
    the stats refresh and the cache scatter.
    """
    n, c = updates.shape
    bn, n_pad, c_pad = _gram_blocks(n, c, block_n, block_c)
    x = jnp.pad(updates.astype(jnp.float32), ((0, n_pad - n),
                                              (0, c_pad - c)))
    return _gram_strip(x, stats, ids, n, lam, bn, block_c, gram_in_bf16,
                       interpret, epilogue)


@functools.partial(jax.jit,
                   static_argnames=("temperature", "lam", "normalize",
                                    "block_n", "block_c", "gram_in_bf16",
                                    "interpret"))
def cached_selection_step_pallas(updates: jnp.ndarray, dist: jnp.ndarray,
                                 stats: jnp.ndarray, ids: jnp.ndarray,
                                 temperature: float, lam: float = 10.0,
                                 normalize: bool = False,
                                 block_n: int = 128, block_c: int = 512,
                                 gram_in_bf16: bool = False,
                                 interpret: bool = True):
    """Incremental HiCS selection step, kernel path.

    (N, C) Δb + cached (dist (N, N), stats (N, 2)) + (K,) refreshed ids
    -> (Ĥ (N,), dist, stats) with rows/cols of ``ids`` recomputed and
    re-symmetrized — O(K·N·C) instead of O(N²·C).  Same epilogue
    arithmetic as ``hics_selection_step_pallas`` (dot-then-divide
    cosine, f32 accumulation), so cached and from-scratch kernels agree
    row-for-row.  K = 0 returns the cache unchanged.
    """
    n, c = updates.shape
    k = ids.shape[0]
    if k == 0:
        return stats[:, 1], dist, stats
    bn, n_pad, c_pad = _gram_blocks(n, c, block_n, block_c)
    k_pad = max(_BK, -(-k // _BK) * _BK)
    x = jnp.pad(updates.astype(jnp.float32), ((0, n_pad - n),
                                              (0, c_pad - c)))
    rows_f32 = jnp.pad(x[ids], ((0, k_pad - k), (0, 0)))  # (k_pad, c_pad)
    inv_t = jnp.full((k_pad, 1), 1.0 / temperature, jnp.float32)
    ent_r, norm_r, rms_r = _fused_stats_padded(rows_f32, inv_t, c, 8,
                                               block_c, interpret)
    if normalize:
        scale = 1.0 / (jnp.clip(rms_r, 1e-12, None)[:, None]
                       * temperature)
        ent_r, _, _ = _fused_stats_padded(rows_f32, scale, c, 8,
                                          block_c, interpret)
    stats = stats.at[ids].set(
        jnp.stack([norm_r[:k], ent_r[:k]], axis=-1))
    strip = _gram_strip(x, stats, ids, n, lam, bn, block_c, gram_in_bf16,
                        interpret)
    dist = dist.at[ids].set(strip)
    dist = dist.at[:, ids].set(strip.T)
    return stats[:, 1], dist, stats


@functools.partial(jax.jit,
                   static_argnames=("metric", "block_n", "block_c",
                                    "gram_in_bf16", "interpret"))
def cached_feature_step_pallas(feats: jnp.ndarray, dist: jnp.ndarray,
                               stats: jnp.ndarray, ids: jnp.ndarray,
                               metric: str = "cosine",
                               block_n: int = 128, block_c: int = 512,
                               gram_in_bf16: bool = False,
                               interpret: bool = True):
    """Incremental FULL-UPDATE distance step (CS/DivFL), kernel path.

    (N, F) flattened-update features + cached (dist (N, N), stats
    (N, 2) = [L2 norm, 0]) + (K,) refreshed ids -> (dist, stats) with
    rows/cols of ``ids`` recomputed through the strip kernel and
    re-symmetrized — O(K·N·F) instead of O(N²·F).  ``metric`` is the
    selector's own distance: "cosine" (Clustered Sampling's angular
    distance) or "l2" (DivFL's Euclidean).  The stats lane layout
    matches the HiCS cache (entropy lane carried as zero) so ONE state
    pytree serves every cached selector.  K = 0 returns the cache
    unchanged; duplicate ids are harmless.
    """
    if metric not in ("cosine", "l2"):
        raise ValueError(f"unknown metric {metric!r}; expected "
                         "'cosine' or 'l2'")
    n, c = feats.shape
    k = ids.shape[0]
    if k == 0:
        return dist, stats
    bn, n_pad, c_pad = _gram_blocks(n, c, block_n, block_c)
    x = jnp.pad(feats.astype(jnp.float32), ((0, n_pad - n),
                                            (0, c_pad - c)))
    rows_f32 = x[ids]                                   # (K, c_pad)
    norms = jnp.sqrt(jnp.sum(rows_f32 * rows_f32, axis=-1))
    stats = stats.at[ids].set(
        jnp.stack([norms, jnp.zeros_like(norms)], axis=-1))
    strip = _gram_strip(x, stats, ids, n, 0.0, bn, block_c, gram_in_bf16,
                        interpret, metric)
    # the oracle's scatter (transpose-averaged K×K block) keeps the
    # exact-symmetry invariant identical across backends
    return ref._scatter_strip_symmetric(dist, strip, ids), stats
