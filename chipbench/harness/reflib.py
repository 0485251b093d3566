"""Plain ``jax.numpy`` pieces the references share.  Nothing here imports
the program: these are the published algorithms written out directly.

* ``fake_quant`` — int8 across the offloading cut: per row of the last axis,
  scale = absmax / 127, round to nearest, clip to [-127, 127], multiply
  back; the gradient passes straight through.
* ``topk_blocks`` — error-feedback sparsification (Stich et al.,
  arXiv:1809.07599) as the server step applies it: every leaf is cut into
  blocks of 1024 (the last one short), each block keeps the
  ``max(1, int(density * valid))`` entries of largest magnitude, the earlier
  index first among equals.
* ``int8_blocks`` — the int8 wire format of a sent delta, per block of 1024
  (zero-padded).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024


def _int8_roundtrip(x: jnp.ndarray) -> jnp.ndarray:
    """Rows of ``x`` (last axis) through absmax int8 and back."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                        1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127)
    return (q * scale).astype(x.dtype)


def fake_quant(x: jnp.ndarray) -> jnp.ndarray:
    """Straight-through int8 of the smashed data."""
    return x + jax.lax.stop_gradient(_int8_roundtrip(x) - x)


def _blocks(leaf: jnp.ndarray):
    n = leaf.size
    nb = -(-n // BLOCK)
    flat = jnp.pad(leaf.reshape(-1), (0, nb * BLOCK - n))
    return flat.reshape(nb, BLOCK), n, nb


def keep_counts(n: int, density: float) -> np.ndarray:
    nb = -(-n // BLOCK)
    valid = np.minimum(BLOCK, n - BLOCK * np.arange(nb))
    return np.maximum(1, np.minimum(valid, np.floor(density * valid + 1e-9)
                                    .astype(np.int64)))


def topk_blocks(leaf: jnp.ndarray, density: float) -> jnp.ndarray:
    xb, n, nb = _blocks(leaf)
    ks = keep_counts(n, density)
    k = jnp.asarray(ks)[:, None]
    valid = jnp.minimum(BLOCK, n - BLOCK * jnp.arange(nb))[:, None]
    lane = jnp.arange(BLOCK)[None]
    mag = jnp.where(lane < valid, jnp.abs(xb).astype(jnp.float32), -1.0)
    # the k-th largest magnitude of each block; all above it are kept, and
    # of those equal to it as many as the budget leaves, earliest first
    kth = jnp.take_along_axis(jax.lax.top_k(mag, int(ks.max()))[0], k - 1,
                              axis=1)
    above = mag > kth
    tie = mag == kth
    room = k - above.sum(axis=1, keepdims=True)
    keep = above | (tie & (jnp.cumsum(tie, axis=1) <= room))
    return jnp.where(keep, xb, jnp.zeros_like(xb)).reshape(-1)[:n] \
        .reshape(leaf.shape)


def int8_blocks(leaf: jnp.ndarray) -> jnp.ndarray:
    xb, n, _ = _blocks(leaf)
    return _int8_roundtrip(xb).reshape(-1)[:n].reshape(leaf.shape)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax cross-entropy, in float32."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def rms_norm(x: jnp.ndarray, scale_minus_one: jnp.ndarray,
             eps: float) -> jnp.ndarray:
    """RMS norm in float32, its scale stored minus one."""
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale_minus_one.astype(jnp.float32))).astype(x.dtype)
