"""Plain reference of the Qwen3 dense decoder (Qwen/Qwen3-0.6B config.json
and the Qwen3 technical report, arXiv:2505.09388): token embedding; per
layer pre-norm (RMS) grouped-query attention with RMS norm on each query
and key head, rotary position embedding (theta from the config, halves
rotated), causal softmax, then a pre-norm SwiGLU MLP, each added to the
residual; a final RMS norm and the tied embedding as the output head.  The
norm scales are stored minus one (zero-initialised), the configuration's
parametrization of the same function.

Weights follow the configuration's init recipe from the seed: the key splits
into embedding, layers and head; each layer's key into attention (q, k, v,
o) and MLP (gate, up, down); matrices are normal / sqrt(fan-in), the
embedding normal * 0.02.  Imports nothing of the program."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.harness.reflib import cross_entropy, fake_quant, rms_norm


def _dims(spec):
    return (spec["hidden_size"], spec["num_attention_heads"],
            spec["num_key_value_heads"], spec["head_dim"],
            spec["intermediate_size"], spec["vocab_size"],
            spec["num_hidden_layers"])


def init(spec: dict, key, dtype=jnp.float32):
    d, H, KV, D, F, V, L = _dims(spec)

    def dense(k, a, b):
        return (jax.random.normal(k, (a, b), jnp.float32)
                / math.sqrt(a)).astype(dtype)

    def layer(k):
        k_attn, k_ffn = jax.random.split(k)
        ka = jax.random.split(k_attn, 4)
        kf = jax.random.split(k_ffn, 3)
        return {"ln1": jnp.zeros((d,), dtype), "ln2": jnp.zeros((d,), dtype),
                "attn": {"wq": dense(ka[0], d, H * D),
                         "wk": dense(ka[1], d, KV * D),
                         "wv": dense(ka[2], d, KV * D),
                         "wo": dense(ka[3], H * D, d),
                         "q_norm": jnp.zeros((D,), dtype),
                         "k_norm": jnp.zeros((D,), dtype)},
                "ffn": {"w_gate": dense(kf[0], d, F),
                        "w_up": dense(kf[1], d, F),
                        "w_down": dense(kf[2], F, d)}}

    k_embed, k_layers, _ = jax.random.split(key, 3)
    embed = (jax.random.normal(k_embed, (V, d), jnp.float32)
             * 0.02).astype(dtype)
    return {"embed": embed,
            "layers": jax.vmap(layer)(jax.random.split(k_layers, L)),
            "final_norm": jnp.zeros((d,), dtype)}


def _rope(x, theta):
    """x (B, S, heads, D) at positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _block(spec, params, i: int, x):
    d, H, KV, D, F, V, L = _dims(spec)
    eps, theta = spec["rms_norm_eps"], float(spec["rope_theta"])
    B, S, _ = x.shape
    lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])

    h = rms_norm(x, lp["ln1"], eps)
    q = rms_norm((h @ lp["attn"]["wq"]).reshape(B, S, H, D),
                 lp["attn"]["q_norm"], eps)
    k = rms_norm((h @ lp["attn"]["wk"]).reshape(B, S, KV, D),
                 lp["attn"]["k_norm"], eps)
    v = (h @ lp["attn"]["wv"]).reshape(B, S, KV, D)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // KV, axis=2)                  # head h -> kv h // G
    v = jnp.repeat(v, H // KV, axis=2)
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # B,H,S,D
    s = (qh @ kh.transpose(0, 1, 3, 2)).astype(jnp.float32) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = (a @ vh).transpose(0, 2, 1, 3).reshape(B, S, H * D)
    x = x + o @ lp["attn"]["wo"]
    h = rms_norm(x, lp["ln2"], eps)
    f = (jax.nn.silu(h @ lp["ffn"]["w_gate"]) * (h @ lp["ffn"]["w_up"])) \
        @ lp["ffn"]["w_down"]
    return x + f


def hidden(spec: dict, params, tokens, op=None, quantize=False):
    """Final-norm hidden states (B, S, d).  ``op`` layers run before the
    cut, which is int8 when ``quantize``."""
    x = params["embed"][tokens]
    for i in range(spec["num_hidden_layers"]):
        if i == op and quantize:
            x = fake_quant(x)
        x = _block(spec, params, i, x)
    return rms_norm(x, params["final_norm"], spec["rms_norm_eps"])


def loss(spec: dict, params, batch, op: int, quantize: bool):
    h = hidden(spec, params, batch["tokens"], op=op,
               quantize=quantize and op < spec["num_hidden_layers"])
    return cross_entropy(h @ params["embed"].T, batch["labels"])


def eval_loss(spec: dict, params, data):
    return loss(spec, params, data, spec["num_hidden_layers"], False)
