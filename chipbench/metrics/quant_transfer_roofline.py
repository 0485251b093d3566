"""Roofline share of the ``quant_transfer`` Pallas kernels (int8 quantize
and dequantize), in %: the least time their bytes need at the chip's HBM
bandwidth over their device time in the trace.  Per call the quantize
kernel reads fp32 rows and writes int8 rows and one fp32 scale per row;
the dequantize kernel reads those and writes fp32.  Each round calls them on
the smashed data of every cut client's local iterations (rows of the last
axis) and on every client's delta in the server step (rows of 1024)."""

import math

import jax

# the two Pallas calls as XLA prints them: a tpu_custom_call that returns
# int8 rows and scales (quantize) or takes them (dequantize)
KERNEL = r"(= \(s8\[|custom-call\(s8\[).*tpu_custom_call"
BLOCK = 1024


def _pair_bytes(elems: float, rows: float) -> float:
    return (4.0 + 1.0) * elems + 4.0 * rows + (1.0 + 4.0) * elems + 4.0 * rows


def cut_shape(ctx, op: int):
    """Shape of the smashed data at ``op`` for one batch."""
    spec, mix = ctx.cell.spec, ctx.cell.mix
    if spec["family"] == "dense":
        return (mix["batch"], mix["seq"], spec["hidden_size"])
    hw, c = spec["input_hw"], spec["input_ch"]
    for layer in spec["layers"][:op]:
        if layer.startswith("C"):
            c = int(layer[1:])
        elif layer == "MP":
            hw //= 2
    return (mix["batch"], hw, hw, c)


def round_bytes(ctx) -> float:
    spec, mix = ctx.cell.spec, ctx.cell.mix
    shapes = jax.eval_shape(lambda k: ctx.cell.ref.init(spec, k),
                            jax.random.PRNGKey(0))
    n = sum(-(-l.size // BLOCK) * BLOCK
            for l in jax.tree_util.tree_leaves(shapes))
    total = mix["clients"] * _pair_bytes(n, n / BLOCK) \
        if mix["quantize_deltas"] else 0.0
    native = ctx.cell.config.native_op(spec)
    if mix["quantize_transfer"]:
        for op, count in mix["ops"].items():
            if int(op) < native:
                shape = cut_shape(ctx, int(op))
                elems = float(math.prod(shape))
                total += count * mix["local_iters"] * _pair_bytes(
                    elems, elems / shape[-1])
    return total


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t = ctx.trace.op_s(KERNEL)
    if t <= 0:
        return None
    need = round_bytes(ctx) * ctx.out["rounds"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need / t
