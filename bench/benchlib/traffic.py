"""The general traffic generator: a cell's federated data from its
workload file and ``--seed``.

A cell's traffic is a client population with non-IID labels, drawn as
the HiCS-FL paper (§4.1, App. A.10) draws it: the clients are split
into equal groups, one per concentration parameter α, each group holds
an equal share of the training samples, and within a group each
class's samples are split over the group's clients by Dir(α)
proportions.  Clients left with fewer than ``min_per_client`` samples
take them from the group's largest client.

The split (how many samples of which class each client holds) is drawn
once from the workload's fixed ``structure_seed``, so every ``--seed``
gives the same set of client sizes and the same padded capacity: the
same work and the same compiled shapes.  ``--seed`` permutes which
client holds which share, relabels the classes, and draws the samples
themselves: a Gaussian mixture with one unit prototype per class (scaled
by ``proto_scale``), a rank-``rank`` within-class subspace and isotropic
noise, the task the program's own synthetic generator describes.

The samples are made on the device in one jitted call; the host builds
only the (N, cap) index layout.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One cell's data, on the device (jax arrays) plus host counts."""
    x: object            # (N, cap, dim) f32, zero rows past a client's size
    y: object            # (N, cap) i32, 0 past a client's size
    mask: object         # (N, cap) f32, 1 on real rows
    test: Dict[str, object]   # {"x": (T, dim), "y": (T,), "mask": (T,)}
    sizes: np.ndarray    # (N,) real rows per client


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


def make_traffic(wl: dict, seed: int) -> Traffic:
    """The cell's client data and test set for ``seed``."""
    data = wl["data"]
    n, c = int(wl["num_clients"]), int(data["num_classes"])
    base = partition_counts(int(wl["structure_seed"]), n, c,
                            int(wl["samples_train"]), wl["alphas"],
                            int(wl.get("min_per_client", 2)))
    np_seed, jax_seed = split_seed(seed)
    rng = np.random.default_rng(np_seed)
    counts = base[rng.permutation(n)][:, rng.permutation(c)]
    sizes = counts.sum(axis=1)
    cap = int(sizes.max())
    ys = _labels(rng, counts)
    y_flat = np.concatenate(ys).astype(np.int32)
    total = y_flat.shape[0]
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    pos = np.arange(cap)[None, :]
    idx = np.where(pos < sizes[:, None], offsets[:, None] + pos,
                   total).astype(np.int32)          # `total` = zero row
    t = int(wl["samples_test"])
    y_test = rng.integers(0, c, size=t).astype(np.int32)
    x, y, m, xt = _draw(jax.random.PRNGKey(jax_seed), jnp.asarray(y_flat),
                        jnp.asarray(idx), jnp.asarray(y_test),
                        int(data["dim"]), int(data["rank"]),
                        float(data["noise"]), float(data["proto_scale"]), c)
    test = {"x": xt, "y": jnp.asarray(y_test),
            "mask": jnp.ones((t,), jnp.float32)}
    return Traffic(x=x, y=y, mask=m, test=test, sizes=sizes)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _draw(key, y_flat, idx, y_test, dim, rank, noise, proto_scale,
          num_classes):
    """Samples of every client (gathered into the (N, cap) layout, the
    index ``len(y_flat)`` giving a zero row) and of the test set."""
    kp, kb, kc, kn, kct, knt = jax.random.split(key, 6)
    protos = jax.random.normal(kp, (num_classes, dim), jnp.float32)
    protos = proto_scale * protos / jnp.linalg.norm(protos, axis=1,
                                                    keepdims=True)
    # (C·rank, dim): row block c is class c's within-class basis
    bases = jax.random.normal(kb, (num_classes * rank, dim),
                              jnp.float32) / np.sqrt(dim)

    def samples(y, kcoef, knoise):
        coef = jax.random.normal(kcoef, (y.shape[0], rank), jnp.float32)
        onehot = jax.nn.one_hot(y, num_classes, dtype=jnp.float32)
        low = (onehot[:, :, None] * coef[:, None, :]).reshape(
            y.shape[0], num_classes * rank)
        return (protos[y] + jnp.dot(low, bases,
                                    precision=jax.lax.Precision.HIGHEST)
                + noise * jax.random.normal(knoise, (y.shape[0], dim),
                                            jnp.float32))

    x_flat = samples(y_flat, kc, kn)
    x_flat = jnp.concatenate([x_flat, jnp.zeros((1, dim), jnp.float32)])
    y_pad = jnp.concatenate([y_flat, jnp.zeros((1,), jnp.int32)])
    live = idx < y_flat.shape[0]
    return (x_flat[idx], y_pad[idx], live.astype(jnp.float32),
            samples(y_test, kct, knt))
