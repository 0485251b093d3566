"""Block-local top-k gradient sparsification as a Pallas TPU kernel.

Implements the paper's future-work item ('techniques such as quantization
may reduce the communication cost') for the cross-pod FedAvg sync: each pod
ships only the k largest-magnitude delta entries per block.

TPU-native design: a *global* top-k needs a sort (hostile to the VPU); a
block-local top-k is embarrassingly parallel over VMEM tiles and empirically
matches global top-k for gradient compression (Deep Gradient Compression,
arXiv:1712.01887, uses the same local-selection trick).  Inside the kernel
the k-th-largest threshold is found with masked-max iterations — vector ops
only, no sort.

Each block carries a ``(valid, k)`` metadata pair: ``valid`` masks padded
lanes out of the selection (a tail block of a padded buffer must not let
zeros/garbage compete for the top-k or inflate the survivor count), and
``k`` is the per-block keep budget — computed by the caller from the *true*
(unpadded) element count so the effective density is honest for leaves
smaller than a block (the density-skew fix; repro.kernels.topk_compress.ops
builds the meta table).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels.compat import CompilerParams


# blocks per grid step: an (8, block) tile is one sublane-aligned row group
# of vregs, and its (8, 2) meta slice satisfies the TPU block rule (second-
# minor dim divisible by 8, minor dim equal to the array's)
ROWS = 8


def _topk_kernel(x_ref, meta_ref, o_ref, *, kmax: int):
    x = x_ref[...].astype(jnp.float32)        # (rows, block)
    valid = meta_ref[:, 0:1]                  # (rows, 1) true lanes per block
    kk = meta_ref[:, 1:2]                     # (rows, 1) budget, 1<=kk<=valid
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    mag = jnp.where(lane < valid, jnp.abs(x), -jnp.inf)

    # kk-th largest *entry* via masked-max rounds (no sort on the VPU).
    # Each round peels one distinct magnitude and advances the cumulative
    # entry count; the threshold is the magnitude at which that count
    # crosses kk (duplicates may cover several ranks in one round, so
    # counting rounds instead of entries would overshoot).  kmax static
    # iterations always suffice: every round retires >= 1 entry.
    def peel(_, carry):
        remaining, kth, cnt = carry
        cur = jnp.max(remaining, axis=1, keepdims=True)
        ncur = jnp.sum(jnp.where(remaining == cur, 1, 0), axis=1,
                       keepdims=True)
        kth = jnp.where((cnt < kk) & (cnt + ncur >= kk), cur, kth)
        remaining = jnp.where(remaining >= cur, -jnp.inf, remaining)
        return remaining, kth, cnt + ncur

    rows = x.shape[0]
    _, kth, _ = jax.lax.fori_loop(
        0, kmax, peel, (mag, jnp.full((rows, 1), jnp.inf, jnp.float32),
                        jnp.zeros((rows, 1), jnp.int32)))
    # tie guard: never keep more than kk entries — of the entries equal to
    # the threshold keep only the first ``quota`` by lane index, taken one
    # lowest-lane tie per round (quota <= kk <= kmax rounds suffice)
    above = mag > kth
    quota = kk - jnp.sum(jnp.where(above, 1, 0), axis=1, keepdims=True)

    def take(j, carry):
        ties, kept = carry
        first = jnp.min(jnp.where(ties > 0, lane, x.shape[1]), axis=1,
                        keepdims=True)
        hit = (lane == first) & (j < quota)
        return (jnp.where(hit, 0, ties), jnp.where(hit, 1, kept))

    _, kept = jax.lax.fori_loop(
        0, kmax, take, (jnp.where(mag == kth, 1, 0), jnp.zeros_like(lane)))
    keep = above | (kept > 0)
    o_ref[...] = jnp.where(keep, x, 0.0).astype(o_ref.dtype)


def topk_compress_pallas(x: jnp.ndarray, meta: jnp.ndarray, kmax: int,
                         block: int = 1024,
                         interpret: bool = False) -> jnp.ndarray:
    """``x`` (n,) with ``n % block == 0``; ``meta`` (n/block, 2) int32 rows of
    ``(valid_lanes, k)`` per block; ``kmax`` static upper bound on k.  Each
    grid step selects within ``ROWS`` blocks at once; a ragged last step
    reads past the end (rows whose output is dropped)."""
    n = x.shape[0]
    assert n % block == 0, f"n {n} % block {block} != 0 (pad upstream)"
    nb = n // block
    assert meta.shape == (nb, 2), f"meta {meta.shape} != ({nb}, 2)"
    rows = min(ROWS, nb)
    kernel = functools.partial(_topk_kernel, kmax=kmax)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nb, rows),),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 2), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), x.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x.reshape(nb, block), meta.astype(jnp.int32))
    return out.reshape(n)
