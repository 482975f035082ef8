"""Agglomerative clustering on a precomputed distance matrix.

The paper (App. A.1.2) groups clients with "an off-the-shelf clustering
algorithm performing hierarchical clustering with Ward's Method" on the
Eq. 9 distance.  scipy is not available offline, so this is a
self-contained numpy implementation of bottom-up agglomerative
clustering with Lance–Williams distance updates:

    ward     (scipy-compatible on squared-distance semantics)
    average  (UPGMA)
    complete / single

The merge loop keeps a lazily-verified per-row minimum cache: the
cached value is always a LOWER bound on the row's true minimum (merges
only update it with ``np.minimum``), and the picked row is verified
with one row argmin — which simultaneously yields the partner column
and reproduces the naive flat-argmin tie order exactly.  Each merge is
then O(N) amortized with ~a dozen vector ops, no per-merge boolean-mask
copies, and no (N, N) argmin.  Rows retired by a merge are parked at
+inf so inactive entries never win.

:func:`agglomerate_device` runs the same merge loop in float32 inside
``jit``: the same cache and pick-time check (a ``while_loop``), so the
same merge order and the same Lance–Williams values, and O(N) work per
merge with no pass over the (N, N) matrix.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_LINKAGES = ("ward", "average", "complete", "single")


def agglomerate(dist: np.ndarray, num_clusters: int,
                linkage: str = "ward",
                precomputed: bool = False) -> np.ndarray:
    """Cluster N items into ``num_clusters`` groups.

    dist: (N, N) symmetric distance matrix (diagonal ignored).
    Returns integer labels (N,) in [0, num_clusters), relabelled by
    first appearance for determinism.  ``precomputed=True`` promises an
    already exactly-symmetric matrix (e.g. the incremental selection
    cache, or a kernel-produced Eq. 9 matrix) and skips the defensive
    ``0.5·(d + dᵀ)`` pass — a numerical no-op on symmetric input, so
    labels are identical either way.
    """
    if linkage not in _LINKAGES:
        raise ValueError(f"linkage must be one of {_LINKAGES}")
    n = dist.shape[0]
    if dist.shape != (n, n):
        raise ValueError(f"distance matrix must be square, got {dist.shape}")
    num_clusters = max(1, min(num_clusters, n))

    # Work on a copy with +inf diagonal; ward operates on squared dists
    # (Lance–Williams ward update is exact in d² space).
    d = np.array(dist, dtype=np.float64)
    if not precomputed:
        d = 0.5 * (d + d.T)
    if linkage == "ward":
        d = d ** 2
    np.fill_diagonal(d, np.inf)

    sizes = np.ones(n, dtype=np.float64)
    # merge forest: parent[j] = i records "cluster j absorbed into i"
    # (always i < j); labels resolve by chasing parents once at the end
    parent = np.arange(n)
    merges = n - num_clusters
    # Lazily-verified nearest-pair cache.  Invariant: row_min[k] ≤ true
    # min of row k for every live row.  Improvements are folded in
    # eagerly (np.minimum); entries that a merge RAISED (the cached
    # best edge pointed at one of the merged clusters) are left
    # stale-low and repaired only if the row is ever picked: the verify
    # argmin over the actual row exposes the true minimum.
    row_min = d.min(axis=1)
    for _ in range(merges):
        while True:
            i = int(np.argmin(row_min))
            j = int(np.argmin(d[i]))        # true row min + tie column
            true_min = d[i, j]
            if true_min == row_min[i]:
                break
            row_min[i] = true_min           # was stale-low: repair, retry
        if i > j:
            i, j = j, i
        dij = d[i, j]
        ni, nj = sizes[i], sizes[j]
        # Lance–Williams update of d(k, i∪j), vectorized over ALL k:
        # retired/self entries are +inf and stay +inf through each
        # formula (no inf−inf terms arise), so no mask copy is needed.
        di, dj = d[i], d[j]
        if linkage == "ward":
            nk = sizes
            new = (ni + nk) * di
            new += (nj + nk) * dj
            new -= nk * dij
            new /= ni + nj + nk
        elif linkage == "average":
            new = ni * di
            new += nj * dj
            new /= ni + nj
        elif linkage == "complete":
            new = np.maximum(di, dj)
        else:  # single
            new = np.minimum(di, dj)
        new[i] = np.inf                      # keep the diagonal +inf
        new[j] = np.inf
        d[i, :] = new
        d[:, i] = new
        # retire j: column only — row j is never read again (row_min[j]
        # goes to +inf below so j is never picked, and row rescans read
        # other rows, whose j-th element this write covers)
        d[:, j] = np.inf
        sizes[i] = ni + nj
        sizes[j] = 0.0
        parent[j] = i

        # --- refresh the min cache (lower bounds only) ----------------
        # Other rows: fold in the new edge to the merged cluster.  Rows
        # whose old minimum sat at column i or j may now be stale-low;
        # the pick-time verify repairs them if it matters.
        np.minimum(row_min, new, out=row_min)
        row_min[i] = new.min()               # row i changed wholesale
        row_min[j] = np.inf                  # retired

    # resolve the merge forest (parents always point to lower indices,
    # so one increasing pass suffices), then relabel 0..M-1 by first
    # appearance
    labels = np.arange(n)
    for k in range(n):
        labels[k] = labels[parent[k]]
    uniq: dict = {}
    out = np.empty(n, dtype=np.int64)
    for k, lab in enumerate(labels):
        if lab not in uniq:
            uniq[lab] = len(uniq)
        out[k] = uniq[lab]
    return out


def agglomerate_device(dist: jnp.ndarray, num_clusters: int,
                       linkage: str = "ward",
                       precomputed: bool = False) -> jnp.ndarray:
    """Pure-jax agglomerative clustering — jit/scan/vmap-compatible.

    Same Lance–Williams semantics as :func:`agglomerate` (ward on
    squared distances, flat-argmin merge order, first-appearance
    relabelling) in float32 with fixed shapes: N − M merges in a
    ``fori_loop``, retired clusters parked at +inf.

    Each merge is O(N): it follows :func:`agglomerate` step for step.
    A per-row minimum cache, always a lower bound on the row's true
    minimum, picks the row ``p = argmin(row_min)``; a small
    ``while_loop`` checks it against the row's true minimum and, while
    the cache was stale-low, repairs that entry and picks again (about
    1.5 repairs a merge on Eq. 9 matrices).  A checked row's minimum is
    ≤ every other row's lower bound, and every earlier row's bound is
    strictly larger, so ``p`` is the first row that holds the global
    minimum and ``q = argmin`` of that row the first column in it:
    exactly the pair (p < q) of the row-major argmin over the whole
    matrix, ties included.  Each of those minima is one reduction that
    also returns the winner's write stamp and size, so a merge runs
    about a dozen small ops plus five a repair.

    A merge writes one row of the matrix, the merged cluster's, and no
    column: a column write next to a row write makes the TPU compiler
    keep the matrix in two layouts and copy all of it twice a merge.
    A row is read as it stands instead: entry c of row k comes from
    row k or from row c, whichever was written later (``stamp``), and
    retired clusters (size 0) read +inf.  No op inside the merge loop
    passes over the (N, N) matrix.

    Because merges always absorb the higher index into the lower, each
    surviving representative r first appears in the label vector at
    position r — so first-appearance relabelling is exactly the rank of
    r among the sorted representatives, which ``unique(size=M)`` +
    ``searchsorted`` computes with static shapes.

    ``precomputed=True`` is the fast path for callers holding an
    already exactly-symmetric distance — the incremental selection
    cache and the fused Eq. 9 kernels both produce one — skipping the
    defensive ``0.5·(d + dᵀ)`` sweep over (N, N).  On symmetric input
    ``0.5·(x + x)`` is bit-exact ``x`` in f32, so the flag can never
    change labels; it only removes work.
    """
    return _agglomerate_device(dist, num_clusters, linkage,
                               precomputed)[0]


class _Pick(NamedTuple):
    """One pick of the merge loop: row ``p`` with the least cached
    minimum ``low``, its true minimum at its first column ``q``, what
    the merge needs of both rows, and the repairs made so far."""
    row_min: jnp.ndarray
    low: jnp.ndarray
    p: jnp.ndarray
    stamp_p: jnp.ndarray
    n_p: jnp.ndarray
    true_min: jnp.ndarray
    q: jnp.ndarray
    stamp_q: jnp.ndarray
    n_q: jnp.ndarray
    repairs: jnp.ndarray


def _agglomerate_device(dist: jnp.ndarray, num_clusters: int,
                        linkage: str = "ward", precomputed: bool = False
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`agglomerate_device` plus the number of stale-row repairs
    the pick loop made over all merges (an int32 scalar)."""
    if linkage not in _LINKAGES:
        raise ValueError(f"linkage must be one of {_LINKAGES}")
    n = dist.shape[0]
    num_clusters = max(1, min(int(num_clusters), n))
    d = jnp.asarray(dist, jnp.float32)
    if not precomputed:
        d = 0.5 * (d + d.T)
    if linkage == "ward":
        d = d * d
    d = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d)

    idx = jnp.arange(n)

    def first_min(x, *rest):
        # (min of x, first index holding it, rest[...] at that index) in
        # one reduction: the argmin's own tie order, plus what the merge
        # needs to know of the winner without a gather of its own
        def keep(a, b):
            take = (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
            return tuple(jnp.where(take, u, v) for u, v in zip(a, b))
        init = (jnp.float32(jnp.inf), jnp.int32(n)) + tuple(
            jnp.zeros((), r.dtype) for r in rest)
        return jax.lax.reduce((x, idx) + rest, init, keep, (0,))

    def row(d, stamp, sizes, k, stamp_k):
        # row k as it stands: entry c lives in row k if row k was
        # written after row c, else in row c (read as column k);
        # retired clusters read +inf
        fresh = jnp.where(stamp > stamp_k, d[:, k], d[k])
        return jnp.where(sizes > 0, fresh, jnp.inf)

    def body(t, carry):
        d, stamp, row_min, sizes, labels, repairs = carry

        def look(row_min, repairs):
            low, p, stamp_p, n_p = first_min(row_min, stamp, sizes)
            true_min, q, stamp_q, n_q = first_min(
                row(d, stamp, sizes, p, stamp_p), stamp, sizes)
            return _Pick(row_min, low, p, stamp_p, n_p, true_min, q,
                         stamp_q, n_q, repairs)

        # the cache is a lower bound, so a picked row is stale exactly
        # when its cached value is below the row's true minimum; the
        # strict test also ends the loop on NaN input
        def repair(c):
            return look(jnp.where(idx == c.p, c.true_min, c.row_min),
                        c.repairs + 1)

        (row_min, _, p, stamp_p, n_p, dij, q, stamp_q, n_q,
         repairs) = jax.lax.while_loop(lambda c: c.low < c.true_min,
                                       repair, look(row_min, repairs))
        dp = row(d, stamp, sizes, p, stamp_p)
        dq = row(d, stamp, sizes, q, stamp_q)
        # p < q on symmetric input; ordering keeps merges absorbing the
        # higher index whatever the input
        swap = p > q
        i, j = jnp.where(swap, q, p), jnp.where(swap, p, q)
        di, dj = jnp.where(swap, dq, dp), jnp.where(swap, dp, dq)
        ni, nj = jnp.where(swap, n_q, n_p), jnp.where(swap, n_p, n_q)
        if linkage == "ward":
            new = ((ni + sizes) * di + (nj + sizes) * dj
                   - sizes * dij) / (ni + nj + sizes)
        elif linkage == "average":
            new = (ni * di + nj * dj) / (ni + nj)
        elif linkage == "complete":
            new = jnp.maximum(di, dj)
        else:  # single
            new = jnp.minimum(di, dj)
        is_i, is_j = idx == i, idx == j
        new = jnp.where(is_i | is_j, jnp.inf, new)
        d = jax.lax.dynamic_update_slice(d, new[None, :], (i, 0))
        row_min = jnp.where(is_i, jnp.min(new),
                            jnp.where(is_j, jnp.inf,
                                      jnp.minimum(row_min, new)))
        sizes = jnp.where(is_i, ni + nj, jnp.where(is_j, 0.0, sizes))
        stamp = jnp.where(is_i, t + 1, stamp)
        labels = jnp.where(labels == j, i, labels)
        return d, stamp, row_min, sizes, labels, repairs

    _, _, _, _, labels, repairs = jax.lax.fori_loop(
        0, n - num_clusters, body,
        (d, jnp.zeros(n, jnp.int32), jnp.min(d, axis=1),
         jnp.ones(n, jnp.float32), idx, jnp.zeros((), jnp.int32)))
    reps = jnp.unique(labels, size=num_clusters)
    return jnp.searchsorted(reps, labels).astype(jnp.int32), repairs


def cluster_means_device(values: jnp.ndarray, labels: jnp.ndarray,
                         num_clusters: int) -> jnp.ndarray:
    """Per-cluster mean via ``segment_sum`` (device analogue of
    :func:`cluster_means`; empty clusters get 0)."""
    s = jax.ops.segment_sum(values, labels, num_segments=num_clusters)
    c = jax.ops.segment_sum(jnp.ones_like(values), labels,
                            num_segments=num_clusters)
    return jnp.where(c > 0, s / jnp.maximum(c, 1.0), 0.0)


def cluster_means(values: np.ndarray, labels: np.ndarray,
                  num_clusters: int) -> np.ndarray:
    """Per-cluster mean of a per-item scalar (e.g. estimated entropy)."""
    out = np.zeros(num_clusters, dtype=np.float64)
    for m in range(num_clusters):
        sel = labels == m
        out[m] = float(np.mean(values[sel])) if np.any(sel) else 0.0
    return out


def silhouette_hint(dist: np.ndarray, labels: np.ndarray) -> float:
    """Mean silhouette over items (diagnostic only; not used to select)."""
    n = dist.shape[0]
    uniq = np.unique(labels)
    if len(uniq) < 2:
        return 0.0
    s = []
    for i in range(n):
        same = labels == labels[i]
        same[i] = False
        a = float(np.mean(dist[i, same])) if np.any(same) else 0.0
        b = min(float(np.mean(dist[i, labels == m]))
                for m in uniq if m != labels[i])
        denom = max(a, b)
        s.append(0.0 if denom == 0 else (b - a) / denom)
    return float(np.mean(s))
