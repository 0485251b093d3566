"""The operation and byte counts that the per-layer metrics divide by: VGG5
FLOPs against a hand count, the LM's 6 N per token plus attention against
XLA's own count at a tiny size, and the kernels' bytes from shapes."""
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import common

BENCH = common.load_benchmark()
BENCH_DIR = common.BENCH_DIR


def _metric(name):
    return common.load_module(BENCH_DIR / "metrics" / f"{name}.py")


def test_vgg5_flops_match_a_hand_count():
    mfu = _metric("train_mfu")
    spec = common.Cell(BENCH, "vgg5.fedadapt-k64").spec
    conv1 = 32 * 32 * 32 * 3 * 9 * 2       # 32x32 out, 3 -> 32 channels
    conv2 = 16 * 16 * 64 * 32 * 9 * 2      # after the first pool
    conv3 = 8 * 8 * 64 * 64 * 9 * 2        # after the second pool
    fc1 = 2 * (8 * 8 * 64) * 128
    fc2 = 2 * 128 * 10
    fwd = conv1 + conv2 + conv3 + fc1 + fc2
    assert mfu.vgg_forward_flops(spec) == fwd
    assert mfu.vgg_train_flops_per_sample(spec) == 3 * fwd - conv1
    assert 50e6 < 3 * fwd < 52e6            # about 51 MFLOP per sample


def test_lm_flops_match_xla_cost_analysis_at_a_tiny_size():
    mfu = _metric("train_mfu")
    c = common.Cell(BENCH, "qwen3-0.6b-cut4.fed-k4")
    spec = dict(c.spec, hidden_size=256, intermediate_size=768,
                num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                num_hidden_layers=2, vocab_size=1024)
    B, S = 2, 128
    params = c.ref.init(spec, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    step = jax.jit(jax.grad(lambda p: c.ref.loss(spec, p, batch, 2, False)))
    xla = step.lower(params).compile().cost_analysis()
    xla = xla[0] if isinstance(xla, list) else xla
    counted = B * S * mfu.lm_train_flops_per_token(spec, S)
    # XLA also counts the elementwise work (norms, softmax, rope, the loss)
    # and needs no input gradient of the embedding lookup
    assert 0.85 < counted / xla["flops"] < 1.15, (counted, xla["flops"])


def test_topk_bytes_from_shapes():
    topk = _metric("topk_compress_roofline")
    c = common.Cell(BENCH, "vgg5.fedadapt-k64")
    ctx = SimpleNamespace(cell=c)
    n = topk.flat_len(ctx)
    sizes = [l.size for l in jax.tree_util.tree_leaves(
        c.ref.init(c.spec, jax.random.PRNGKey(0)))]
    assert n == sum(math.ceil(s / 1024) * 1024 for s in sizes)
    assert topk.round_bytes(ctx) == 64 * (8 * n + 8 * n / 1024)


def test_quant_bytes_from_shapes():
    q = _metric("quant_transfer_roofline")
    c = common.Cell(BENCH, "qwen3-0.6b-cut4.fed-k4")
    ctx = SimpleNamespace(cell=c)
    assert q.cut_shape(ctx, 1) == (8, 512, 1024)
    n = sum(math.ceil(l.size / 1024) * 1024 for l in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: c.ref.init(c.spec, k),
                       jax.random.PRNGKey(0))))
    per_pair = lambda elems, rows: 10 * elems + 8 * rows    # noqa: E731
    cut = 2 * 2 * per_pair(8 * 512 * 1024, 8 * 512)         # 2 clients x 2
    server = 4 * per_pair(n, n / 1024)
    assert q.round_bytes(ctx) == pytest.approx(cut + server)
    v = common.Cell(BENCH, "vgg5.fedadapt-k64")
    vctx = SimpleNamespace(cell=v)
    assert q.cut_shape(vctx, 2) == (100, 16, 16, 32)
    assert q.cut_shape(vctx, 4) == (100, 8, 8, 64)
    assert q.cut_shape(vctx, 5) == (100, 8, 8, 64)


def test_peaks_are_keyed_by_device_kind():
    from chipbench.harness.report import peaks_for
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_reference_topk_keeps_the_budget_with_ties_to_the_earlier_index():
    from chipbench.harness import reflib
    x = np.zeros(2048 + 300, np.float32)
    x[:1024] = np.tile([3.0, -3.0, 1.0, 0.5], 256)     # ties at the top
    x[1024:2048] = np.arange(1024, dtype=np.float32)
    x[2048:] = -np.arange(300, dtype=np.float32)
    out = np.asarray(reflib.topk_blocks(jnp.asarray(x), 0.01))
    keep = np.flatnonzero(out)
    assert list(keep[keep < 1024]) == [0, 1, 4, 5, 8, 9, 12, 13, 16, 17]
    assert list(keep[(keep >= 1024) & (keep < 2048)]) == \
        list(range(2038, 2048))
    # a short last block keeps int(0.01 * 300) = 3
    assert list(keep[keep >= 2048]) == [2048 + 297, 2048 + 298, 2048 + 299]
    np.testing.assert_array_equal(out[keep], x[keep])
