"""Roofline share of the ``topk_compress`` Pallas kernel, in %: the least
time its bytes need at the chip's HBM bandwidth over its device time in
the trace (matched by its operands, the name XLA gives it being a
generic ``closed_call``).  The kernel is memory-bound: per call it reads one client's
flat fp32 row and its per-block (valid, k) table and writes the sparse
row; the server step calls it once per client each round."""

import jax

# the Pallas call as XLA prints it: a tpu_custom_call on one f32 row tiled
# (blocks, 1024) and the s32 (blocks, 2) table of (valid, k)
KERNEL = r"custom-call\(f32\[\d+,\d+\][^%]*%[\w.\-]+, s32\[\d+,2\].*tpu_custom_call"
BLOCK = 1024


def flat_len(ctx) -> int:
    """The flat buffer: every leaf padded to whole blocks."""
    shapes = jax.eval_shape(lambda k: ctx.cell.ref.init(ctx.cell.spec, k),
                            jax.random.PRNGKey(0))
    return sum(-(-l.size // BLOCK) * BLOCK
               for l in jax.tree_util.tree_leaves(shapes))


def round_bytes(ctx) -> float:
    n = flat_len(ctx)
    return ctx.cell.mix["clients"] * (4.0 * n + 4.0 * n + 8.0 * n / BLOCK)


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t = ctx.trace.op_s(KERNEL)
    if t <= 0:
        return None
    need = round_bytes(ctx) * ctx.out["rounds"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need / t
