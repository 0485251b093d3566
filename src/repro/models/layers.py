"""Shared neural-net building blocks (pure JAX, functional).

Conventions
-----------
* params are nested dicts of ``jnp.ndarray``; init fns take an rng key and a
  dtype.  No framework (flax/optax are not installed in this container).
* activations:   x  (batch, seq, d_model)
* attention:     q  (batch, seq, heads, head_dim), k/v (batch, seq, kv, head_dim)
* norms and softmax accumulate in float32 regardless of param dtype.
* ``use_pallas`` switches the attention/SSD hot-spots to the Pallas kernels in
  ``repro.kernels`` (TPU target); the default jnp path is the oracle used on
  CPU and for the dry-run lowering.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

Params = Dict[str, jnp.ndarray]

# -----------------------------------------------------------------------------
# scan wrapper: the dry-run's cost accounting needs loop bodies *unrolled*
# (XLA cost_analysis counts a while-loop body once, regardless of trip count),
# while the production lowering keeps compact scans.  All model-zoo scans go
# through ``scan`` so launch/dryrun.py can flip the switch per lowering.
# -----------------------------------------------------------------------------
_UNROLL = threading.local()


@contextlib.contextmanager
def unroll_scans(on: bool = True):
    prev = getattr(_UNROLL, "on", False)
    _UNROLL.on = on
    try:
        yield
    finally:
        _UNROLL.on = prev


def scan(f, init, xs, length=None):
    unroll = getattr(_UNROLL, "on", False)
    return lax.scan(f, init, xs, length=length,
                    unroll=True if unroll else 1)


@contextlib.contextmanager
def moe_int8_gather(on: bool = True):
    """§Perf toggle: int8-compress the MoE FSDP weight all-gathers."""
    prev = getattr(_UNROLL, "moe_int8_gather", False)
    _UNROLL.moe_int8_gather = on
    try:
        yield
    finally:
        _UNROLL.moe_int8_gather = prev


# =============================================================================
# initializers
# =============================================================================
def _dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def _embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# =============================================================================
# norms
# =============================================================================
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def init_rms_norm(d: int, dtype) -> jnp.ndarray:
    # stored as (scale - 1) so zeros-init == identity (gemma convention)
    return jnp.zeros((d,), dtype)


# =============================================================================
# rotary embeddings
# =============================================================================
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                         # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# =============================================================================
# attention
# =============================================================================
def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(x / cap)


def attention_scores_mask(
    q_pos: jnp.ndarray,       # (Sq,) int32
    k_pos: jnp.ndarray,       # (Sk,) int32 (may contain -1 for invalid slots)
    causal: bool,
    window,                   # int or traced int32 scalar; <=0 => full attention
) -> jnp.ndarray:
    """Boolean (Sq, Sk) mask. window>0 keeps k in (q-window, q].

    ``window`` may be a traced scalar (per-layer window values are scanned
    over for local/global alternating archs like gemma2)."""
    valid = k_pos[None, :] >= 0
    if causal:
        valid &= k_pos[None, :] <= q_pos[:, None]
    window = jnp.asarray(window, jnp.int32)
    in_window = k_pos[None, :] > (q_pos[:, None] - window)
    valid &= jnp.where(window > 0, in_window, True)
    return valid


def multi_head_attention(
    q: jnp.ndarray,           # (B, Sq, H, D)
    k: jnp.ndarray,           # (B, Sk, KV, D)
    v: jnp.ndarray,           # (B, Sk, KV, D)
    mask: jnp.ndarray,        # (Sq, Sk) or (B, Sq, Sk) bool
    attn_softcap: float = 0.0,
) -> jnp.ndarray:
    """Reference grouped-query attention (GQA); returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    if attn_softcap > 0.0:
        scores = softcap(scores, attn_softcap)
    if mask.ndim == 2:
        m = mask[None, None, None, :, :]
    else:
        m = mask[:, None, None, :, :]
    scores = jnp.where(m, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, D)


def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": _dense_init(ks[0], cfg.d_model, cfg.q_dim, dtype),
        "wk": _dense_init(ks[1], cfg.d_model, cfg.kv_dim, dtype),
        "wv": _dense_init(ks[2], cfg.d_model, cfg.kv_dim, dtype),
        "wo": _dense_init(ks[3], cfg.q_dim, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(cfg.head_dim, dtype)
        p["k_norm"] = init_rms_norm(cfg.head_dim, dtype)
    return p


def attention_block(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,                    # (B, S, d)
    positions: jnp.ndarray,            # (S,) int32
    *,
    window: int,
    kv_cache: Optional[Params] = None,  # {"k","v": (B, W, KV, D)} rolling buffers
    cache_len: int = 0,                 # W (static); 0 => training (no cache)
    decode_pos: Optional[jnp.ndarray] = None,  # int32 during decode: scalar
                                               # (whole batch at one position)
                                               # or (B,) per-row positions
                                               # (continuous batching)
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Self-attention with optional rolling-buffer KV cache.

    Training / prefill: kv_cache=None, full-sequence causal(+window) attention.
    Decode: x is (B, 1, d); cache slots are written at ``decode_pos % W``.
    A vector ``decode_pos`` gives every batch row its own position — the
    serving engine's slot pool, where concurrent requests sit at different
    sequence depths (``positions`` is then (B, S) instead of (S,)).
    """
    B, S, d = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    pos_b = positions if positions.ndim == 2 else positions[None, :]
    q = apply_rope(q, pos_b, cfg.rope_theta)
    k = apply_rope(k, pos_b, cfg.rope_theta)

    if kv_cache is None:
        mask = attention_scores_mask(positions, positions, causal=True, window=window)
        out = multi_head_attention(q, k, v, mask, cfg.attn_softcap)
        new_cache = None
    elif decode_pos is not None and jnp.ndim(decode_pos) == 1:
        # per-row decode positions: each row writes its own slot and masks
        # against its own position (mask is (B, 1, W))
        W = cache_len
        slot = decode_pos % W                                    # (B,)
        ck = kv_cache["k"].at[jnp.arange(B), slot].set(k[:, 0])
        cv = kv_cache["v"].at[jnp.arange(B), slot].set(v[:, 0])
        idx = jnp.arange(W)
        dp = decode_pos[:, None]                                 # (B, 1)
        k_pos = dp - ((dp - idx) % W)                            # (B, W)
        mask = (k_pos >= 0) & (k_pos <= dp)
        window_t = jnp.asarray(window, jnp.int32)
        mask &= jnp.where(window_t > 0, k_pos > dp - window_t, True)
        out = multi_head_attention(q, ck, cv, mask[:, None, :],
                                   cfg.attn_softcap)
        new_cache = {"k": ck, "v": cv}
    else:
        W = cache_len
        slot = decode_pos % W
        ck = kv_cache["k"].at[:, slot].set(k[:, 0])
        cv = kv_cache["v"].at[:, slot].set(v[:, 0])
        # position stored in each slot s: latest q <= pos with q % W == s
        idx = jnp.arange(W)
        k_pos = decode_pos - ((decode_pos - idx) % W)
        mask = (k_pos >= 0)[None, :] & (k_pos <= decode_pos)[None, :]  # (1, W)
        window_t = jnp.asarray(window, jnp.int32)
        in_window = (k_pos > decode_pos - window_t)[None, :]
        mask &= jnp.where(window_t > 0, in_window, True)
        out = multi_head_attention(q, ck, cv, mask, cfg.attn_softcap)
        new_cache = {"k": ck, "v": cv}
    y = out.reshape(B, S, cfg.q_dim) @ p["wo"]
    return y, new_cache


# =============================================================================
# feed-forward
# =============================================================================
def init_ffn(key, d: int, f: int, act: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {
            "w_gate": _dense_init(ks[0], d, f, dtype),
            "w_up": _dense_init(ks[1], d, f, dtype),
            "w_down": _dense_init(ks[2], f, d, dtype),
        }
    return {
        "w_up": _dense_init(ks[0], d, f, dtype),
        "w_down": _dense_init(ks[1], f, d, dtype),
    }


def ffn(p: Params, x: jnp.ndarray, act: str) -> jnp.ndarray:
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif act == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]


# =============================================================================
# mixture of experts (token-choice top-k, capacity-bounded, sort-free)
#
# Two execution paths:
#  * moe_block_local — the plain math (single-device / smoke tests).
#  * sharded path (used automatically when sharding rules are active) — a
#    shard_map over the mesh: tokens stay local to their data shard, each
#    model-rank computes only its expert shard (arctic: E/tp experts; mixtral:
#    all experts but d_ff/tp), FSDP weight shards are all-gathered over
#    'data', and outputs psum over 'model'.  Without this, XLA's SPMD
#    partitioner replicates the scatter/cumsum dispatch chain across the
#    whole mesh (~256x FLOP blow-up, caught by the dry-run roofline).
# =============================================================================
def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    assert cfg.moe is not None
    E, d, f = cfg.moe.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p: Params = {
        "router": _dense_init(ks[0], d, E, dtype),
        "w_gate": (jax.random.normal(ks[1], (E, d, f), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, f), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, f, d), jnp.float32) / math.sqrt(f)).astype(dtype),
    }
    if cfg.moe.dense_residual:
        p["dense"] = init_ffn(ks[4], d, f, cfg.mlp_act, dtype)
    return p


def moe_block(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Token-choice top-k MoE. Dispatches to the shard_map expert-parallel
    path when sharding rules are active (see banner above), else local."""
    from repro.parallel.sharding import current_rules
    rules = current_rules()
    if rules is not None and rules.tp:
        tp = rules.axis_size(rules.tp)
        if cfg.moe.num_experts % tp == 0 or cfg.d_ff % tp == 0:
            return _moe_block_sharded(cfg, p, x, rules)
    return _moe_block_local(cfg, p, x)


def _moe_block_local(cfg: ModelConfig, p: Params, x: jnp.ndarray
                     ) -> jnp.ndarray:
    """Token-choice top-k MoE with capacity; static shapes; no global sort.

    Dispatch positions are computed with a cumulative-sum over the one-hot
    assignment matrix (GShard-style but materializing only (T*k, E) int32),
    then tokens are scattered into an (E*C, d) buffer, expert FFNs run as a
    single batched einsum, and results are combined with the top-k weights.
    Overflow beyond capacity C is dropped (standard).
    """
    assert cfg.moe is not None
    B, S, d = x.shape
    E, k_top = cfg.moe.num_experts, cfg.moe.top_k
    T = B * S
    xf = x.reshape(T, d)

    gate_logits = (xf @ p["router"]).astype(jnp.float32)       # (T, E)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    topw, topi = lax.top_k(probs, k_top)                       # (T, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)        # renormalize (mixtral)

    if S == 1:
        # decode step: exact, drop-free, FLOPs proportional to active tokens
        out = _moe_decode_exact(cfg, p, xf, topw, topi).reshape(B, S, d)
        if cfg.moe.dense_residual:
            out = out + ffn(p["dense"], x, cfg.mlp_act)
        return out

    C = max(1, int(cfg.moe.capacity_factor * T * k_top / E))
    flat_e = topi.reshape(-1)                                  # (T*k,)
    assign = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (T*k, E)
    pos_all = jnp.cumsum(assign, axis=0) - assign              # pos within expert
    pos = jnp.take_along_axis(pos_all, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)            # overflow -> scratch row
    token_idx = jnp.arange(T * k_top) // k_top

    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xf[token_idx])
    h = buf[: E * C].reshape(E, C, d)
    if cfg.mlp_act == "swiglu":
        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["w_gate"]))
    else:
        act = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", h, p["w_gate"]))
    mid = act * jnp.einsum("ecd,edf->ecf", h, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", mid, p["w_down"]).reshape(E * C, d)

    w_flat = topw.reshape(-1).astype(x.dtype)                  # (T*k,)
    gathered = y[jnp.minimum(slot, E * C - 1)]                 # (T*k, d)
    contrib = jnp.where(keep[:, None], w_flat[:, None] * gathered, 0.0)
    out = jnp.zeros((T, d), x.dtype).at[token_idx].add(contrib)
    out = out.reshape(B, S, d)

    if cfg.moe.dense_residual:
        out = out + ffn(p["dense"], x, cfg.mlp_act)
    return out


def _moe_block_sharded(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                       rules) -> jnp.ndarray:
    """Expert-parallel MoE under shard_map.

    Tokens stay on their (pod, data) shard; along the 'model' axis either
      * case A — experts are sharded (E % tp == 0, arctic): each rank
        dispatches its local tokens to its E/tp experts only, or
      * case B — d_ff is sharded (mixtral): each rank runs all experts on a
        d_ff/tp slice.
    FSDP ('data'-sharded) weight dims are all-gathered inside the body (the
    FSDP unshard, visible in the collective roofline term) and the partial
    outputs psum over 'model'.  Decode steps (S == 1) use a lossless
    capacity C = T_local * k, so serving never drops tokens.
    """
    from jax.sharding import PartitionSpec as P

    assert cfg.moe is not None
    int8_gather = getattr(_UNROLL, "moe_int8_gather", False)
    mesh = rules.mesh
    E, k_top = cfg.moe.num_experts, cfg.moe.top_k
    d, f = cfg.d_model, cfg.d_ff
    B, S, _ = x.shape
    tp_ax = rules.tp[0]
    tp = mesh.shape[tp_ax]
    fsdp_ax = rules.fsdp[0] if rules.fsdp else None
    fsdp = mesh.shape.get(fsdp_ax, 1) if fsdp_ax else 1
    expert_sharded = E % tp == 0
    d_sh = fsdp_ax if (fsdp_ax and d % fsdp == 0) else None
    f_sh = tp_ax if (not expert_sharded and f % tp == 0) else None
    b_axes = rules.resolve("batch", B)

    x_spec = P(b_axes, None, None)
    router_spec = P(d_sh, None)
    if expert_sharded:
        wg_spec = P(tp_ax, d_sh, None)
        wd_spec = P(tp_ax, None, d_sh)
    else:
        wg_spec = P(None, d_sh, f_sh)
        wd_spec = P(None, f_sh, d_sh)
    dense = cfg.moe.dense_residual
    dense_f_sh = tp_ax if (dense and f % tp == 0) else None
    dg_spec = P(d_sh, dense_f_sh)
    dd_spec = P(dense_f_sh, d_sh)

    in_specs = [x_spec, router_spec, wg_spec, wg_spec, wd_spec]
    operands = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    if dense:
        in_specs += [dg_spec, dg_spec, dd_spec]
        operands += [p["dense"]["w_gate"], p["dense"]["w_up"],
                     p["dense"]["w_down"]]

    def _gather_w(w, axis):
        """FSDP unshard of an expert-weight shard; optionally int8-compressed
        (rowwise absmax over the last dim) — §Perf iteration: halves the
        dominant collective term of expert-sharded MoE at <0.4% weight RMS
        error (the paper's quantization future-work applied to weights).
        Straight-through custom VJP: the gradient path stays exact (the
        cotangent psum-scatters back to the shard, as for a plain gather)."""
        if not int8_gather:
            return lax.all_gather(w, fsdp_ax, axis=axis, tiled=True)

        # quantize along an axis that is NOT being gathered, so the scales
        # gather consistently alongside the int8 payload
        q_axis = w.ndim - 2 if axis == w.ndim - 1 else w.ndim - 1

        @jax.custom_vjp
        def g(w):
            absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=q_axis,
                             keepdims=True)
            scale = jnp.maximum(absmax, 1e-12) / 127.0
            q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                         -127, 127).astype(jnp.int8)
            qg = lax.all_gather(q, fsdp_ax, axis=axis, tiled=True)
            sg = lax.all_gather(scale, fsdp_ax, axis=axis, tiled=True)
            return (qg.astype(jnp.float32) * sg).astype(w.dtype)

        def g_fwd(w):
            return g(w), None

        def g_bwd(_, ct):
            return (lax.psum_scatter(ct, fsdp_ax, scatter_dimension=axis,
                                     tiled=True),)

        g.defvjp(g_fwd, g_bwd)
        return g(w)

    def body(xb, router, wg, wu, wd, *dense_w):
        if d_sh is not None:
            router = lax.all_gather(router, fsdp_ax, axis=0, tiled=True)
            wg = _gather_w(wg, 1)
            wu = _gather_w(wu, 1)
            wd = _gather_w(wd, 2)
        Bl, Sl, _ = xb.shape
        xf = xb.reshape(-1, d)
        T_l = xf.shape[0]
        gate_logits = (xf @ router).astype(jnp.float32)
        probs = jax.nn.softmax(gate_logits, axis=-1)
        topw, topi = lax.top_k(probs, k_top)
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

        if expert_sharded:
            local_E = E // tp
            e0 = lax.axis_index(tp_ax) * local_E
        else:
            local_E = E
            e0 = 0
        if Sl == 1:                       # decode: lossless capacity
            C = T_l * k_top
        else:
            C = max(1, int(cfg.moe.capacity_factor * T_l * k_top / E))
        flat_e = topi.reshape(-1) - e0                      # local expert idx
        in_range = (flat_e >= 0) & (flat_e < local_E)
        safe_e = jnp.where(in_range, flat_e, local_E)
        assign = jax.nn.one_hot(safe_e, local_E + 1, dtype=jnp.int32)
        pos_all = jnp.cumsum(assign, axis=0) - assign
        pos = jnp.take_along_axis(pos_all, safe_e[:, None], axis=1)[:, 0]
        keep = in_range & (pos < C)
        slot = jnp.where(keep, safe_e * C + pos, local_E * C)
        token_idx = jnp.arange(T_l * k_top) // k_top

        buf = jnp.zeros((local_E * C + 1, d), xb.dtype).at[slot].set(
            xf[token_idx])
        h = buf[: local_E * C].reshape(local_E, C, d)
        if cfg.mlp_act == "swiglu":
            act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg))
        else:
            act = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", h, wg))
        mid = act * jnp.einsum("ecd,edf->ecf", h, wu)
        y = jnp.einsum("ecf,efd->ecd", mid, wd).reshape(local_E * C, d)

        w_flat = topw.reshape(-1).astype(xb.dtype)
        gathered = y[jnp.minimum(slot, local_E * C - 1)]
        contrib = jnp.where(keep[:, None], w_flat[:, None] * gathered, 0.0)
        out = jnp.zeros((T_l, d), xb.dtype).at[token_idx].add(contrib)

        if dense_w:
            dg, du, dd = dense_w
            if d_sh is not None:
                dg = lax.all_gather(dg, fsdp_ax, axis=0, tiled=True)
                du = lax.all_gather(du, fsdp_ax, axis=0, tiled=True)
                dd = lax.all_gather(dd, fsdp_ax, axis=1, tiled=True)
            if cfg.mlp_act == "swiglu":
                hd = jax.nn.silu(xf @ dg) * (xf @ du)
            else:
                hd = jax.nn.gelu(xf @ dg) * (xf @ du)
            dense_out = hd @ dd
            if dense_f_sh is None and (expert_sharded or f_sh is not None):
                # experts are tp-summed but the dense branch is replicated
                dense_out = dense_out / tp
            out = out + dense_out

        out = lax.psum(out, tp_ax)
        return out.reshape(Bl, Sl, d)

    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=x_spec, check_vma=False,
    )(*operands)


def _moe_decode_exact(cfg: ModelConfig, p: Params, xf: jnp.ndarray,
                      topw: jnp.ndarray, topi: jnp.ndarray) -> jnp.ndarray:
    """Drop-free MoE for decode (one token per row).

    Sorts the (T*k) assignments by expert and runs grouped matmuls via
    ``lax.ragged_dot`` (FLOPs proportional to actual tokens — no capacity
    over-compute, no drops).  Used only when S == 1; training/prefill keep
    the capacity-based dispatch (GShard semantics)."""
    E, k_top = cfg.moe.num_experts, cfg.moe.top_k
    T, d = xf.shape
    flat_e = topi.reshape(-1)
    order = jnp.argsort(flat_e)                         # (T*k,)
    token_idx = order // k_top
    rows = xf[token_idx]                                # (T*k, d) sorted by expert
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)

    def gmm(lhs, rhs):                                  # (m,k) x (E,k,n)
        return lax.ragged_dot(lhs, rhs, group_sizes)

    if cfg.mlp_act == "swiglu":
        act = jax.nn.silu(gmm(rows, p["w_gate"]))
    else:
        act = jax.nn.gelu(gmm(rows, p["w_gate"]))
    mid = act * gmm(rows, p["w_up"])
    y = gmm(mid, p["w_down"])                           # (T*k, d)
    w_sorted = topw.reshape(-1)[order].astype(xf.dtype)
    return jnp.zeros((T, d), xf.dtype).at[token_idx].add(w_sorted[:, None] * y)


def moe_aux_loss(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style)."""
    assert cfg.moe is not None
    B, S, d = x.shape
    E, k_top = cfg.moe.num_experts, cfg.moe.top_k
    gate_logits = (x.reshape(-1, d) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    _, topi = lax.top_k(probs, k_top)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(axis=1), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac_tokens * frac_probs)


# =============================================================================
# chunked cross-entropy (never materializes (B, S, V) logits for the bwd)
# =============================================================================
def chunked_ce_loss(
    hidden: jnp.ndarray,         # (B, S, d)
    unembed: jnp.ndarray,        # (d, V)
    labels: jnp.ndarray,         # (B, S) int32; -1 = ignore
    logit_softcap_val: float = 0.0,
    chunk: int = 1024,
) -> jnp.ndarray:
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    n = S // chunk
    assert S % chunk == 0, f"seq {S} must be divisible by loss chunk {chunk}"
    hs = hidden.reshape(B, n, chunk, d).swapaxes(0, 1)         # (n, B, c, d)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(h, lab):
        logits = (h @ unembed).astype(jnp.float32)             # (B, c, V)
        if logit_softcap_val > 0.0:
            logits = softcap(logits, logit_softcap_val)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1
        )[..., 0]
        valid = (lab >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * valid), jnp.sum(valid)

    def body(carry, xs):
        h, lab = xs
        tl, tc = chunk_loss(h, lab)
        return (carry[0] + tl, carry[1] + tc), None

    (total, count), _ = scan(body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ls))
    return total / jnp.maximum(count, 1.0)
