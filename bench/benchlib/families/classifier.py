"""The classifier family: the HiCS-FL paper's models, whose configuration
files list their ``layers`` (the plain reference is ``layers.py``).

Traffic: the population of ``traffic.split`` with each row drawn from a
Gaussian mixture, one unit prototype per class (scaled by
``proto_scale``), a rank-``rank`` within-class subspace and isotropic
noise: the task the program's own synthetic generator describes.  Rows
are (dim,) float32 features with one label each.

The head update HiCS-FL reads is the output layer's bias change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import layers, traffic


def make_traffic(wl: dict, seed: int) -> traffic.Traffic:
    """The cell's client data and test set for ``seed``."""
    data = wl["data"]
    c = int(data["num_classes"])
    s = traffic.split(wl, seed, c)
    x, y, m, xt = _draw(s.key, jnp.asarray(s.y_flat), jnp.asarray(s.idx),
                        jnp.asarray(s.y_test), int(data["dim"]),
                        int(data["rank"]), float(data["noise"]),
                        float(data["proto_scale"]), c)
    test = {"x": xt, "y": jnp.asarray(s.y_test),
            "mask": jnp.ones((s.y_test.shape[0],), jnp.float32)}
    return traffic.Traffic(x=x, y=y, mask=m, test=test, sizes=s.sizes)


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


def program_model(cfg: dict, wl: dict):
    """The program's classifier for the configuration's registry name:
    (init(key), apply(params, x))."""
    from repro.configs import get_config
    from repro.models.classifier import make_classifier
    init, apply, _ = make_classifier(get_config(cfg["registry"]),
                                     input_dim=int(wl["data"]["dim"]))
    return init, apply


def init_params(cfg: dict, key):
    return layers.init_params(cfg, key)


def loss(cfg: dict, params, x, y, mask):
    """Masked mean cross-entropy of a (B, dim) batch over its real rows."""
    logits = layers.forward(cfg, params, x).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, y[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    return jnp.sum(jnp.where(m > 0, nll, 0.0) * m) / jnp.maximum(
        jnp.sum(m), 1.0)


def head_update(cfg: dict, new, old):
    """(K, C): each client's output-layer bias minus the global one."""
    head = cfg["layers"][-1]["name"]
    return new[head]["b"] - old[head]["b"][None]


def train_flops_per_row(cfg: dict, wl: dict) -> int:
    return layers.train_flops_per_sample(cfg)
