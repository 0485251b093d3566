"""jit'd wrappers: int8 transfer compression + straight-through fake-quant
used inside ``models.split.split_loss`` (differentiable through the cut).

``interpret=None`` resolves per backend via ``kernels.compat``: compiled
on TPU, interpreter elsewhere (explicit bool overrides for tests)."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.compat import resolve_interpret
from repro.kernels.quant_transfer.quant_transfer import (
    dequantize_pallas,
    quantize_pallas,
)


@partial(jax.jit, static_argnames=("interpret",))
def quantize(x: jnp.ndarray, interpret: Optional[bool] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Any-shape tensor -> (int8 same-shape, fp32 scales over leading dims)."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    R = flat.shape[0]
    br = 256
    pad = (-R) % min(br, R) if R else 0
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    q, s = quantize_pallas(flat, block_rows=min(br, flat.shape[0]),
                           interpret=resolve_interpret(interpret))
    return (q[:R].reshape(shape),
            s[:R, 0].reshape(shape[:-1]))


@partial(jax.jit, static_argnames=("interpret",))
def dequantize(q: jnp.ndarray, scales: jnp.ndarray,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    shape = q.shape
    flat = q.reshape(-1, shape[-1])
    sflat = scales.reshape(-1, 1)
    R = flat.shape[0]
    br = 256
    pad = (-R) % min(br, R) if R else 0
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        sflat = jnp.pad(sflat, ((0, pad), (0, 0)))
    out = dequantize_pallas(flat, sflat, block_rows=min(br, flat.shape[0]),
                            interpret=resolve_interpret(interpret))
    return out[:R].reshape(shape)


@jax.custom_vjp
def fake_quant_int8(x: jnp.ndarray) -> jnp.ndarray:
    """Quant+dequant with a straight-through gradient: what the model 'sees'
    when the smashed data crosses the cut as int8."""
    q, s = quantize(x)
    return dequantize(q, s).astype(x.dtype)


def _fq_fwd(x):
    return fake_quant_int8(x), None


def _fq_bwd(_, g):
    return (g,)   # straight-through


fake_quant_int8.defvjp(_fq_fwd, _fq_bwd)
