"""Whole-round model FLOP utilization of a training cell, in %: the
forward and backward FLOPs of every client's samples on both sides of the
cut, counted from shapes (recomputation not counted), over the host-clock
round time, the chips and the chip's bf16 peak.  The parameters are
float32, and the program's default-precision matmuls run one bf16 pass,
so the bf16 peak is the denominator.

A configuration's reference module may give ``train_flops(spec, mix)`` per
round; otherwise the counters below serve the families they know."""


def vgg_forward_flops(spec) -> float:
    """Forward FLOPs of one sample: 2*k*k*Cin*Cout per output pixel of a
    convolution (SAME, stride 1), 2*in*out per fully connected layer; the
    pools, norms and activations are not matmul work and are left out."""
    hw, c, flat, total, k = spec["input_hw"], spec["input_ch"], None, 0.0, \
        spec["conv_kernel"]
    for layer in spec["layers"]:
        if layer.startswith("C"):
            out = int(layer[1:])
            total += 2.0 * hw * hw * k * k * c * out
            c = out
        elif layer == "MP":
            hw //= 2
        else:
            units = int(layer[2:])
            fan_in = flat if flat is not None else hw * hw * c
            total += 2.0 * fan_in * units
            flat = units
    return total


def vgg_train_flops_per_sample(spec) -> float:
    """Forward once, backward twice (input and weight gradients), except
    that the first layer needs no input gradient."""
    k, c0 = spec["conv_kernel"], spec["input_ch"]
    first = 2.0 * spec["input_hw"] ** 2 * k * k * c0 * int(spec["layers"][0][1:])
    return 3.0 * vgg_forward_flops(spec) - first


def lm_matmul_params(spec) -> float:
    d, f = spec["hidden_size"], spec["intermediate_size"]
    q = spec["num_attention_heads"] * spec["head_dim"]
    kv = spec["num_key_value_heads"] * spec["head_dim"]
    layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return spec["num_hidden_layers"] * layer + d * spec["vocab_size"]


def lm_train_flops_per_token(spec, seq: int) -> float:
    """6 N per token for the N matmul weights (the tied head included), and
    12 L H D S for the scores and the weighted sum of causal attention
    computed over the whole S x S square, forward and backward."""
    L, H, D = (spec["num_hidden_layers"], spec["num_attention_heads"],
               spec["head_dim"])
    return 6.0 * lm_matmul_params(spec) + 12.0 * L * H * D * seq


def round_flops(ctx) -> float:
    spec, mix = ctx.cell.spec, ctx.cell.mix
    own = getattr(ctx.cell.ref, "train_flops", None)
    if own is not None:
        return own(spec, mix)
    samples = mix["clients"] * mix["local_iters"] * mix["batch"]
    if spec["family"] == "vgg":
        return samples * vgg_train_flops_per_sample(spec)
    if spec["family"] == "dense":
        return samples * mix["seq"] * lm_train_flops_per_token(
            spec, mix["seq"])
    return None


def read(ctx):
    if ctx.peaks is None:
        return None
    flops = round_flops(ctx)
    if flops is None:
        return None
    rounds, window = ctx.out["rounds"], ctx.out["window_s"]
    chips = ctx.cell.chips
    return 100.0 * flops * rounds / (window * chips
                                     * ctx.peaks["bf16_flops_per_s"])
