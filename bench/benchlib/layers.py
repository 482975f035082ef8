"""Plain reference of the classifier family a configuration file
describes: initialisation, forward pass and operation counts, read from
the file's ``layers`` list.

Layer kinds:

* ``conv``: 'SAME' k×k convolution (stride 1, NHWC), bias, ReLU, then a
  2×2 stride-2 max pool with 'SAME' padding when ``pool`` is true.
* ``dense``: x·W + b, then ReLU unless ``relu`` is false.

A layer's weight is drawn from its own key, the i-th of
``jax.random.split(key, len(layers))``: ``normal`` draws N(0, 1) times
``scale``; ``fan_in`` draws a normal truncated to ±2 times
1/√fan_in.  Biases start at zero.  The parameters are a dict of
``{"w", "b"}`` per layer name, the layout the program's classifiers use.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp


def _weight_shape(layer: dict) -> tuple:
    if layer["kind"] == "conv":
        k = int(layer["kernel"])
        return (k, k, int(layer["in"]), int(layer["out"]))
    return (int(layer["in"]), int(layer["out"]))


def init_params(cfg: dict, key) -> Dict[str, dict]:
    layers: List[dict] = cfg["layers"]
    keys = jax.random.split(key, len(layers))
    out = {}
    for k, layer in zip(keys, layers):
        shape = _weight_shape(layer)
        if layer["init"] == "normal":
            w = float(layer["scale"]) * jax.random.normal(k, shape,
                                                          jnp.float32)
        elif layer["init"] == "fan_in":
            w = (1.0 / math.sqrt(shape[-2])) * jax.random.truncated_normal(
                k, -2.0, 2.0, shape, jnp.float32)
        else:
            raise ValueError(f"unknown init {layer['init']!r}")
        out[layer["name"]] = {"w": w,
                              "b": jnp.zeros((shape[-1],), jnp.float32)}
    return out


def _pool2(h):
    """2×2 stride-2 max pool, 'SAME': pad odd sides with -inf."""
    b, hh, ww, c = h.shape
    ph, pw = hh % 2, ww % 2
    h = jnp.pad(h, ((0, 0), (0, ph), (0, pw), (0, 0)),
                constant_values=-jnp.inf)
    h = h.reshape(b, (hh + ph) // 2, 2, (ww + pw) // 2, 2, c)
    return jnp.max(h, axis=(2, 4))


def forward(cfg: dict, params, x):
    """(B, dim) flattened inputs -> (B, num_classes) logits."""
    inp = cfg["input"]
    h = x
    if cfg["layers"][0]["kind"] == "conv":
        h = x.reshape(x.shape[0], int(inp["side"]), int(inp["side"]),
                      int(inp["channels"]))
    for layer in cfg["layers"]:
        p = params[layer["name"]]
        if layer["kind"] == "conv":
            h = jax.lax.conv_general_dilated(
                h, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            h = jax.nn.relu(h + p["b"])
            if layer.get("pool"):
                h = _pool2(h)
        else:
            if h.ndim > 2:
                h = h.reshape(h.shape[0], -1)
            h = h @ p["w"] + p["b"]
            if layer.get("relu", True):
                h = jax.nn.relu(h)
    return h


def forward_macs(cfg: dict) -> int:
    """Multiply-accumulates of one sample's forward pass (weights only:
    conv and dense products; bias, ReLU and pooling are not counted)."""
    inp = cfg["input"]
    side = int(inp.get("side", 0))
    total = 0
    for layer in cfg["layers"]:
        if layer["kind"] == "conv":
            k = int(layer["kernel"])
            total += side * side * k * k * int(layer["in"]) * int(layer["out"])
            if layer.get("pool"):
                side = -(-side // 2)
        else:
            total += int(layer["in"]) * int(layer["out"])
    return total


def train_flops_per_sample(cfg: dict) -> int:
    """Forward plus backward of one sample: 2 FLOPs per MAC forward,
    twice that backward (input and weight gradients)."""
    return 6 * forward_macs(cfg)
