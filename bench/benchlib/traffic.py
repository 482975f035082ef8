"""What every family's traffic shares: a client population with
non-IID labels, drawn from a cell's workload file and ``--seed``.

The population is drawn as the HiCS-FL paper (§4.1, App. A.10) draws
it: the clients are split into equal groups, one per concentration
parameter α, each group holds an equal share of the training samples,
and within a group each class's samples are split over the group's
clients by Dir(α) proportions.  Clients left with fewer than
``min_per_client`` samples take them from the group's largest client.
A family whose rows carry no class (a language model's) reads the
classes as topics.

The split (how many samples of which class each client holds) is drawn
once from the workload's fixed ``structure_seed``, so every ``--seed``
gives the same set of client sizes and the same padded capacity: the
same work and the same compiled shapes.  ``--seed`` permutes which
client holds which share, relabels the classes, and gives the key from
which the family draws the rows themselves (``families/<family>.py``,
``make_traffic``), on the device in one jitted call; the host builds
only the (N, cap) index layout.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One cell's data, on the device (jax arrays) plus host counts.
    Rows are whatever the family trains on, of any dtype: a classifier's
    (dim,) float features, a language model's int32 token ids."""
    x: object            # (N, cap, ...) rows, zero past a client's size
    y: object            # (N, cap, ...) i32 targets, 0 past a client's size
    mask: object         # (N, cap, ...) f32, 1 on real rows (or tokens)
    test: Dict[str, object]   # {"x": (T, ...), "y": (T, ...), "mask": ...}
    sizes: np.ndarray    # (N,) real rows per client


@dataclasses.dataclass(frozen=True)
class Split:
    """A cell's client population for one seed, on the host: the label
    of every training row and where each client's rows sit."""
    sizes: np.ndarray    # (N,) real rows per client
    y_flat: np.ndarray   # (total,) i32 labels of all rows, client by client
    idx: np.ndarray      # (N, cap) i32 row of y_flat; ``total`` past a size
    y_test: np.ndarray   # (samples_test,) i32 labels of the test set
    key: object          # the jax key the family draws the rows from


def split_seed(seed: int) -> tuple:
    """Two 31-bit seeds (numpy, jax) from any whole ``--seed``: JAX's
    PRNGKey keeps only 32 bits of a larger integer, so a seed past 2**32
    would otherwise alias a small one.  The program's own seed is the
    jax one; the data key and the program's keys come from it by
    different splits."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(a) & 0x7FFFFFFF, int(b) & 0x7FFFFFFF


def _largest_remainder(props: np.ndarray, total: int) -> np.ndarray:
    raw = props * total
    counts = np.floor(raw).astype(np.int64)
    rem = int(total - counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:rem]] += 1
    return counts


def partition_counts(structure_seed: int, num_clients: int,
                     num_classes: int, samples: int,
                     alphas: Sequence[float],
                     min_per_client: int = 2) -> np.ndarray:
    """(N, C) samples per client and class, from the structure seed."""
    rng = np.random.default_rng(structure_seed)
    counts = np.zeros((num_clients, num_classes), np.int64)
    groups = np.array_split(np.arange(num_clients), len(alphas))
    shares = [len(s) for s in np.array_split(np.arange(samples),
                                             len(alphas))]
    for alpha, group, share in zip(alphas, groups, shares):
        per_class = _largest_remainder(
            np.full(num_classes, 1.0 / num_classes), share)
        sub = np.zeros((len(group), num_classes), np.int64)
        for c in range(num_classes):
            props = rng.dirichlet(np.full(len(group), float(alpha)))
            sub[:, c] = _largest_remainder(props, int(per_class[c]))
        sizes = sub.sum(axis=1)
        for k in range(len(group)):
            while sizes[k] < min_per_client:
                donors = sizes.copy()
                donors[k] = -1
                d = int(np.argmax(donors))
                if sizes[d] <= min_per_client:
                    break
                c = int(rng.choice(num_classes, p=sub[d] / sizes[d]))
                sub[d, c] -= 1
                sub[k, c] += 1
                sizes[d] -= 1
                sizes[k] += 1
        counts[group] = sub
    return counts


def _labels(rng: np.random.Generator, counts: np.ndarray) -> List[np.ndarray]:
    out = []
    for row in counts:
        y = np.repeat(np.arange(row.shape[0], dtype=np.int32), row)
        rng.shuffle(y)
        out.append(y)
    return out


def split(wl: dict, seed: int, num_classes: int) -> Split:
    """The cell's client population for ``seed``, over ``num_classes``
    classes (or topics)."""
    n, c = int(wl["num_clients"]), int(num_classes)
    base = partition_counts(int(wl["structure_seed"]), n, c,
                            int(wl["samples_train"]), wl["alphas"],
                            int(wl.get("min_per_client", 2)))
    np_seed, jax_seed = split_seed(seed)
    rng = np.random.default_rng(np_seed)
    counts = base[rng.permutation(n)][:, rng.permutation(c)]
    sizes = counts.sum(axis=1)
    cap = int(sizes.max())
    y_flat = np.concatenate(_labels(rng, counts)).astype(np.int32)
    total = y_flat.shape[0]
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    pos = np.arange(cap)[None, :]
    idx = np.where(pos < sizes[:, None], offsets[:, None] + pos,
                   total).astype(np.int32)          # `total` = zero row
    y_test = rng.integers(0, c, size=int(wl["samples_test"])).astype(
        np.int32)
    return Split(sizes=sizes, y_flat=y_flat, idx=idx, y_test=y_test,
                 key=jax.random.PRNGKey(jax_seed))
