"""Pallas TPU kernel: Ward's whole merge loop with the matrix in VMEM.

``core.clustering._merge_loop`` runs the N − M merges of agglomerative
clustering as an XLA ``fori_loop``: some twenty small ops a merge, each
a launch that moves O(N) floats, and column reads of a row-major
(N, N) matrix in HBM.  This kernel runs the same loop in one launch.
It copies the matrix into VMEM once (squaring it for ward, parking the
diagonal at +inf) and runs every merge against VMEM, step for step the
XLA loop's:

- the lower-bound row-minimum cache, checked at pick time by a
  ``while_loop`` that repairs stale-low entries;
- the pick of the first row holding the least cached minimum and the
  first column holding that row's minimum (the flat row-major argmin's
  tie order);
- :func:`lance_williams`, the one float32 expression both paths
  evaluate;
- absorbing the higher index into the lower.

So both give the same labels and the same repair count.

Layout.  The matrix lives lane-block-major: entry (r, c) sits at row
``(c // 128) * rows + r``, lane ``c % 128`` of a (blocks · rows, 128)
scratch.  Column c is then one contiguous (rows, 128) slab at lane
``c % 128``, and row r is one strided load of ``blocks`` sublanes: a
dense (blocks, 128) vector, the layout every per-item vector of the
loop (cached minima, sizes, labels) shares.  The kernel keeps the
matrix symmetric: a merge writes the merged cluster's row, and its
column from columns i and j, which hold the same values as rows i and
j.  Hence the input must be exactly symmetric, as the selection cache
and ``0.5·(d + dᵀ)`` both are.

Grid: one step per block of input rows, pipelined from HBM; the merges
run on the last step.  ``vmap`` adds a leading grid axis, and each
matrix is loaded and clustered in turn.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of one column pass; the matrix's rows pad to a multiple of it
_ROWS = 64
#: the most VMEM the resident matrix may take (v5e has 128 MiB a core)
_VMEM_BUDGET = 96 * 2 ** 20
#: the most VMEM one buffer of pipelined input rows may take
_CHUNK_BYTES = 2 * 2 ** 20


def _layout(n: int) -> Tuple[int, int]:
    """(padded rows, 128-lane blocks) of the VMEM matrix for n items."""
    return -(-n // _ROWS) * _ROWS, -(-n // 128)


def fits_vmem(n: int) -> bool:
    """Whether the n-item matrix fits the VMEM budget."""
    rows, blocks = _layout(n)
    return rows * blocks * 128 * 4 <= _VMEM_BUDGET


def lance_williams(linkage: str, di, dj, dij, ni, nj, nk):
    """d(k, i ∪ j) from d(k, i), d(k, j), d(i, j) and the cluster sizes
    (ward on squared distances), in the one order of float32 operations
    that every merge loop uses."""
    if linkage == "ward":
        return ((ni + nk) * di + (nj + nk) * dj - nk * dij) / (ni + nj + nk)
    if linkage == "average":
        return (ni * di + nj * dj) / (ni + nj)
    if linkage == "complete":
        return jnp.maximum(di, dj)
    return jnp.minimum(di, dj)                          # single


def _ward_merge_kernel(x_ref, labels_ref, repairs_ref, d_ref, rmin_ref,
                       sizes_ref, col_sizes_ref, *, n, merges,
                       linkage, rows, blocks, chunk):
    inf = jnp.float32(jnp.inf)
    step = pl.program_id(0)
    idx = (jax.lax.broadcasted_iota(jnp.int32, (blocks, 128), 0) * 128
           + jax.lax.broadcasted_iota(jnp.int32, (blocks, 128), 1))

    @pl.when(step == 0)
    def _init():
        rmin_ref[...] = jnp.full_like(rmin_ref, inf)

    # input rows [r0, r0 + chunk) into the lane-block-major matrix:
    # squared for ward, +inf on the diagonal and outside n × n; the
    # column minima are the row minima of a symmetric matrix
    r0 = step * chunk
    r = r0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 128), 0)
    for b in range(blocks):
        x = x_ref[:, b * 128:(b + 1) * 128]
        if linkage == "ward":
            x = x * x
        c = b * 128 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 128), 1)
        x = jnp.where((r == c) | (r >= n) | (c >= n), inf, x)
        d_ref[pl.ds(pl.multiple_of(b * rows + r0, _ROWS), chunk), :] = x
        rmin_ref[b:b + 1, :] = jnp.minimum(
            rmin_ref[b:b + 1, :], jnp.min(x, axis=0, keepdims=True))

    @pl.when(step == pl.num_programs(0) - 1)
    def _merge_all():
        sizes_ref[...] = jnp.where(idx < n, 1.0, 0.0).astype(jnp.float32)
        col_sizes_ref[...] = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 0) < n,
            1.0, 0.0).astype(jnp.float32)
        labels_ref[...] = idx

        def row(k):
            # row k as it stands; retired clusters (size 0) read +inf
            return jnp.where(sizes_ref[...] > 0,
                             d_ref[pl.ds(k, blocks, stride=rows), :], inf)

        def first_min(x):
            # least value and the first index holding it, kept in range
            low = jnp.min(x)
            return low, jnp.minimum(jnp.min(jnp.where(x == low, idx, n)),
                                    n - 1)

        def look(repairs):
            low, p = first_min(rmin_ref[...])
            true_min, q = first_min(row(p))
            return low, p, true_min, q, repairs

        def repair(carry):
            _, p, true_min, _, repairs = carry
            rmin_ref[...] = jnp.where(idx == p, true_min, rmin_ref[...])
            return look(repairs + 1)

        def merge(_, repairs):
            # the cache is a lower bound: a picked row is stale exactly
            # when its cached value is below the row's true minimum
            _, p, dij, q, repairs = jax.lax.while_loop(
                lambda c: c[0] < c[2], repair, look(repairs))
            i, j = jnp.minimum(p, q), jnp.maximum(p, q)
            sizes = sizes_ref[...]
            ni = jnp.max(jnp.where(idx == i, sizes, 0.0))
            nj = jnp.max(jnp.where(idx == j, sizes, 0.0))
            new = lance_williams(linkage, row(i), row(j), dij, ni, nj, sizes)
            is_i, is_j = idx == i, idx == j
            new = jnp.where(is_i | is_j, inf, new)

            # column i: the same values down the column, from columns i
            # and j; rows i and j (sized 0 meanwhile) and retired rows
            # read +inf, as they do in the row
            col_sizes_ref[pl.ds(i, 1), :] = jnp.zeros((1, 128), jnp.float32)
            col_sizes_ref[pl.ds(j, 1), :] = jnp.zeros((1, 128), jnp.float32)
            li = i % 128
            shift = (li - j % 128) % 128
            lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 128), 1)

            def column(t, _):
                at = pl.multiple_of(t * _ROWS, _ROWS)
                ci_at = pl.multiple_of((i // 128) * rows + at, _ROWS)
                cj_at = pl.multiple_of((j // 128) * rows + at, _ROWS)
                ci = d_ref[pl.ds(ci_at, _ROWS), :]
                cj = pltpu.roll(d_ref[pl.ds(cj_at, _ROWS), :], shift, 1)
                nk = col_sizes_ref[pl.ds(at, _ROWS), :]
                v = jnp.where(nk > 0,
                              lance_williams(linkage, ci, cj, dij, ni, nj,
                                             nk), inf)
                d_ref[pl.ds(ci_at, _ROWS), :] = jnp.where(lane == li, v, ci)
                return 0

            jax.lax.fori_loop(0, rows // _ROWS, column, 0)
            d_ref[pl.ds(i, blocks, stride=rows), :] = new
            col_sizes_ref[pl.ds(i, 1), :] = jnp.full((1, 128), ni + nj,
                                                     jnp.float32)
            rmin_ref[...] = jnp.where(
                is_i, jnp.min(new),
                jnp.where(is_j, inf, jnp.minimum(rmin_ref[...], new)))
            sizes_ref[...] = jnp.where(is_i, ni + nj,
                                       jnp.where(is_j, 0.0, sizes))
            labels = labels_ref[...]
            labels_ref[...] = jnp.where(labels == j, i, labels)
            return repairs

        repairs = jax.lax.fori_loop(0, merges, merge, jnp.int32(0))
        repairs_ref[...] = jnp.full((1, 128), repairs, jnp.int32)


def ward_merge(dist: jnp.ndarray, num_clusters: int, linkage: str = "ward",
               interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(N, N) exactly symmetric float32 distance -> (labels (N,) int32,
    repairs int32).

    Runs the N − M merges of ``core.clustering._merge_loop`` in one
    kernel, ``num_clusters`` (M) already clamped to [1, N].  A label is
    the lowest index of the item's cluster (the caller relabels by
    first appearance); repairs counts the stale-row repairs of the pick
    loop over all merges.  N must satisfy :func:`fits_vmem`.
    """
    n = dist.shape[0]
    rows, blocks = _layout(n)
    lanes = blocks * 128
    # a block of input rows: a multiple of _ROWS that divides rows
    per = rows // _ROWS
    most = max(1, _CHUNK_BYTES // (_ROWS * lanes * 4))
    chunk = _ROWS * max(g for g in range(1, min(per, most) + 1)
                        if per % g == 0)
    vmem = 4 * (rows * lanes + rows * 128 + 2 * chunk * lanes
                + 6 * 8 * lanes)
    labels, repairs = pl.pallas_call(
        functools.partial(_ward_merge_kernel, n=n, merges=n - num_clusters,
                          linkage=linkage, rows=rows, blocks=blocks,
                          chunk=chunk),
        grid=(rows // chunk,),
        in_specs=[pl.BlockSpec((chunk, lanes), lambda s: (s, 0))],
        out_specs=[pl.BlockSpec((blocks, 128), lambda s: (0, 0)),
                   pl.BlockSpec((1, 128), lambda s: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((blocks, 128), jnp.int32),
                   jax.ShapeDtypeStruct((1, 128), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((blocks * rows, 128), jnp.float32),
                        pltpu.VMEM((blocks, 128), jnp.float32),
                        pltpu.VMEM((blocks, 128), jnp.float32),
                        pltpu.VMEM((rows, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + 8 * 2 ** 20),
        interpret=interpret,
        name="ward_merge",
    )(dist.astype(jnp.float32))
    return labels.reshape(-1)[:n], repairs[0, 0]
