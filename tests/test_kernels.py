"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs the ref.py
pure-jnp oracles (interpret=True executes the Pallas body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.quant_transfer.ops import (
    dequantize,
    fake_quant_int8,
    quantize,
)
from repro.kernels.quant_transfer.ref import dequant_ref, quant_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref, ssd_sequential
from repro.kernels.topk_compress.ops import compress_tree, topk_compress
from repro.kernels.topk_compress.ref import topk_compress_ref

KEY = jax.random.PRNGKey(0)


# =============================================================================
# flash attention
# =============================================================================
FLASH_CASES = [
    # B, Sq, Sk, H, KV, D, causal, window, softcap, dtype
    (2, 128, 128, 4, 2, 64, True, 0, 0.0, jnp.float32),
    (1, 256, 256, 8, 8, 64, True, 64, 0.0, jnp.float32),
    (2, 100, 100, 8, 2, 32, True, 0, 50.0, jnp.float32),
    (1, 128, 384, 4, 1, 64, False, 0, 0.0, jnp.float32),
    (1, 64, 64, 2, 2, 128, True, 32, 30.0, jnp.float32),
    (2, 128, 128, 4, 4, 64, True, 0, 0.0, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "B,Sq,Sk,H,KV,D,causal,window,cap,dtype", FLASH_CASES)
def test_flash_attention_vs_ref(B, Sq, Sk, H, KV, D, causal, window, cap,
                                dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_block_shape_invariance():
    """Different VMEM block shapes must give identical results."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 192, 4, 64))
    k = jax.random.normal(ks[1], (1, 192, 2, 64))
    v = jax.random.normal(ks[2], (1, 192, 2, 64))
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk)
            for bq, bk in [(64, 64), (128, 64), (64, 128), (192, 192)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


# =============================================================================
# ssd scan
# =============================================================================
SSD_CASES = [
    (2, 64, 4, 16, 16, 16, jnp.float32),
    (1, 128, 2, 32, 32, 32, jnp.float32),
    (2, 96, 4, 16, 16, 32, jnp.float32),    # padded seq
    (1, 64, 2, 16, 16, 64, jnp.float32),    # chunk == seq
]


@pytest.mark.parametrize("B,S,H,P,N,chunk,dtype", SSD_CASES)
def test_ssd_scan_vs_sequential(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y_seq, _ = ssd_sequential(x, dt, A, Bm, Cm)
    y_chunk, _ = ssd_ref(x, dt, A, Bm, Cm, chunk)
    y_pal = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_seq),
                               atol=5e-4, rtol=5e-4)


# =============================================================================
# topk compress
# =============================================================================
@pytest.mark.parametrize("interpret", [True, None],
                         ids=["pallas-interpret", "backend-default"])
@pytest.mark.parametrize("n,k,block", [(2048, 16, 1024), (4096, 64, 512),
                                       (1024, 1, 1024), (512, 512, 512)])
def test_topk_vs_ref(n, k, block, interpret):
    """Both implementations — the Pallas kernel body (interpret=True) and
    the backend-default (vectorized jnp on CPU) — match the oracle."""
    x = jax.random.normal(jax.random.fold_in(KEY, n + k), (n,))
    out = topk_compress(x, k, block, interpret=interpret)
    ref = topk_compress_ref(x, min(k, block), block)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert int(jnp.sum(out != 0)) == (n // block) * min(k, block)


def test_topk_kernel_matches_jnp_path_masked_blocks():
    """Pallas kernel vs the vectorized jnp path on the hard cases: partial
    tails, sub-block buffers, and ties crossing the threshold."""
    from repro.kernels.topk_compress.ops import topk_compress_density
    for n, d, seed in [(1500, 0.02, 0), (100, 0.05, 1), (2048, 0.25, 2)]:
        x = jax.random.normal(jax.random.fold_in(KEY, seed), (n,))
        np.testing.assert_array_equal(
            np.asarray(topk_compress_density(x, d, interpret=True)),
            np.asarray(topk_compress_density(x, d)))
    # crafted ties: duplicated magnitudes straddle the k-th threshold
    t = jnp.asarray([5.0, -3.0, 3.0, 3.0, -5.0, 1.0, 0.5, 0.25] * 16)
    np.testing.assert_array_equal(
        np.asarray(topk_compress(t, 3, 128, interpret=True)),
        np.asarray(topk_compress(t, 3, 128)))


@pytest.mark.parametrize("nb,block,kmax", [(13, 128, 7), (8, 256, 5),
                                           (3, 128, 9), (21, 128, 12)])
def test_topk_kernel_row_tiles_match_blocks_ref(nb, block, kmax):
    """The kernel selects within 8 blocks per grid step (a ragged last step
    when 8 does not divide the block count); per-block (valid, k) budgets,
    masked blocks and heavy ties must still select exactly the entries
    ``_topk_blocks_ref`` selects."""
    from repro.kernels.topk_compress.ops import _topk_blocks_ref
    from repro.kernels.topk_compress.topk_compress import topk_compress_pallas
    rng = np.random.RandomState(nb)
    x = rng.randint(-4, 5, size=(nb, block)).astype(np.float32)
    x[::3] = rng.randn(len(x[::3]), block)
    valid = rng.randint(1, block + 1, size=nb)
    valid[nb // 2] = 0                       # a fully masked block
    k = np.clip(rng.randint(1, kmax + 1, size=nb), 1, np.maximum(valid, 1))
    meta = jnp.asarray(np.stack([valid, k], 1).astype(np.int32))
    out = topk_compress_pallas(jnp.asarray(x.reshape(-1)), meta, kmax, block,
                               interpret=True)
    ref = _topk_blocks_ref(jnp.asarray(x), meta, kmax).reshape(-1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_topk_density_from_true_size():
    """The density-skew fix: k comes from the true (unpadded) element count,
    so leaves smaller than a block and padded tails keep ~density * n
    entries — not the full-block budget."""
    from repro.kernels.topk_compress.ops import topk_compress_density
    y = jax.random.normal(jax.random.fold_in(KEY, 1), (100,))
    out = topk_compress_density(y, 0.05)
    assert int(jnp.sum(out != 0)) == 5          # was min(51, 100) pre-fix
    # multi-block with a partial tail: 1500 = 1024 + 476
    z = jax.random.normal(jax.random.fold_in(KEY, 2), (1500,))
    out2 = topk_compress_density(z, 0.02)
    assert int(jnp.sum(out2 != 0)) == \
        int(0.02 * 1024 + 1e-9) + int(0.02 * 476 + 1e-9)
    # kept entries really are the largest |.| within each block
    kept = np.flatnonzero(np.asarray(out2[:1024]))
    thresh = np.sort(np.abs(np.asarray(z[:1024])))[-len(kept)]
    assert (np.abs(np.asarray(z))[kept] >= thresh).all()


def test_topk_explicit_k_scales_tail_budget():
    """Explicit-k API on a padded tail: the tail block keeps a
    proportionally scaled budget over its true lanes only."""
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (1536,))
    out = topk_compress(x, 64, 1024)           # tail: 512 lanes -> k=32
    assert int(jnp.sum(out[:1024] != 0)) == 64
    assert int(jnp.sum(out[1024:] != 0)) == 32


def test_compress_tree_density_honest_per_leaf():
    tree = {"big": jax.random.normal(KEY, (2048,)),
            "small": jax.random.normal(jax.random.fold_in(KEY, 4), (40,))}
    comp, _ = compress_tree(tree, None, density=0.05)
    assert int(jnp.sum(comp["big"] != 0)) == 2 * int(0.05 * 1024)
    assert int(jnp.sum(comp["small"] != 0)) == 2   # max(1, int(.05*40))


def test_error_feedback_telescopes():
    """compressed_t + error_t == carried_t for every round (no signal lost)."""
    tree = {"w": jax.random.normal(KEY, (4096,))}
    err = None
    carried_total = np.zeros(4096)
    sent_total = np.zeros(4096)
    for i in range(4):
        g = {"w": jax.random.normal(jax.random.fold_in(KEY, i), (4096,))}
        carried_total += np.asarray(g["w"])
        comp, err = compress_tree(g, err, density=0.05)
        sent_total += np.asarray(comp["w"])
    # after the last round, unsent residual == error feedback
    np.testing.assert_allclose(sent_total + np.asarray(err["w"]),
                               carried_total, atol=1e-4)


# =============================================================================
# quant transfer
# =============================================================================
@pytest.mark.parametrize("shape", [(256, 64), (3, 100, 32), (7, 13, 128)])
def test_quant_vs_ref(shape):
    x = jax.random.normal(KEY, shape) * 5
    q, s = quantize(x)
    qr, sr = quant_ref(x.reshape(-1, shape[-1]))
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(q.reshape(-1, shape[-1]),
                                          np.int32),
                               np.asarray(qr, np.int32), atol=1)
    recon = dequantize(q, s)
    ref_recon = dequant_ref(qr, sr).reshape(shape)
    np.testing.assert_allclose(np.asarray(recon), ref_recon, atol=1e-3)
    # rowwise error bound: |x - recon| <= scale/2 (+eps for the atol=1 tie)
    err = np.abs(np.asarray(x) - np.asarray(recon))
    bound = np.asarray(s)[..., None] * 1.0 + 1e-6
    assert (err <= bound).all()


def test_fake_quant_straight_through_grad():
    x = jax.random.normal(KEY, (64, 32))
    g = jax.grad(lambda t: jnp.sum(fake_quant_int8(t) * 3.0))(x)
    np.testing.assert_allclose(np.asarray(g), 3.0, atol=1e-6)
