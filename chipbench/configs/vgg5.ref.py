"""Plain reference of VGG-5 (arXiv:2107.04271, Table IV) across an
offloading cut: 3x3 SAME convolutions, each with its bias, batch norm on
the batch's own statistics and ReLU; 2x2 max pools; ReLU between the fully
connected layers; softmax cross-entropy.  The weights follow the
configuration's init recipe from the seed: per layer a key split off in
order, He-style normal weights (1/sqrt(fan-in)), zero biases, unit norm
scales.  Imports nothing of the program."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.harness.reflib import cross_entropy, fake_quant


def init(spec: dict, key, dtype=jnp.float32):
    params, in_c, hw, flat = [], spec["input_ch"], spec["input_hw"], None
    ks = spec["conv_kernel"]
    for layer in spec["layers"]:
        key, sub = jax.random.split(key)
        if layer.startswith("C"):
            out = int(layer[1:])
            w = jax.random.normal(sub, (ks, ks, in_c, out), jnp.float32)
            params.append({"w": (w / math.sqrt(ks * ks * in_c)).astype(dtype),
                           "b": jnp.zeros((out,), dtype),
                           "bn_scale": jnp.ones((out,), dtype),
                           "bn_bias": jnp.zeros((out,), dtype)})
            in_c = out
        elif layer == "MP":
            params.append({})
            hw //= 2
        else:
            units = int(layer[2:])
            fan_in = flat if flat is not None else hw * hw * in_c
            w = jax.random.normal(sub, (fan_in, units), jnp.float32)
            params.append({"w": (w / math.sqrt(fan_in)).astype(dtype),
                           "b": jnp.zeros((units,), dtype)})
            flat = units
    return params


def _layer(spec, i, p, x):
    layer = spec["layers"][i]
    if layer.startswith("C"):
        x = jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
        xf = x.astype(jnp.float32)
        mean = xf.mean(axis=(0, 1, 2))
        var = ((xf - mean) ** 2).mean(axis=(0, 1, 2))
        x = ((xf - mean) / jnp.sqrt(var + 1e-5)).astype(x.dtype) \
            * p["bn_scale"] + p["bn_bias"]
        return jnp.maximum(x, 0)
    if layer == "MP":
        B, H, W, C = x.shape
        return x.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))
    x = x.reshape(x.shape[0], -1) @ p["w"] + p["b"]
    return x if i == len(spec["layers"]) - 1 else jnp.maximum(x, 0)


def logits(spec: dict, params, images, op: int, quantize: bool):
    """Layers [0, op) on the client, int8 across the cut when ``quantize``
    and the cut is inside the model, layers [op, L) on the server."""
    x = images
    n = len(spec["layers"])
    for i in range(n):
        if i == op and quantize and op < n:
            x = fake_quant(x)
        x = _layer(spec, i, params[i], x)
    return x


def loss(spec: dict, params, batch, op: int, quantize: bool):
    return cross_entropy(logits(spec, params, batch["images"], op, quantize),
                         batch["labels"])


def eval_loss(spec: dict, params, data) -> jnp.ndarray:
    n = len(spec["layers"])
    return loss(spec, params, data, n, False)
