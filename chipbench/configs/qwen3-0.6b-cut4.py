"""qwen3-0.6b-cut4: the program's configuration and the cell's data, from
qwen3-0.6b-cut4.json.

``program_config`` builds the ``ModelConfig`` the program runs, key by key
from the published config.json names; ``make_data`` makes each client's
token sequences and the eval set from a seed."""
from __future__ import annotations

import numpy as np


def program_config(spec: dict):
    from repro.configs.base import ModelConfig
    if spec["hidden_act"] != "silu" or spec["attention_bias"]:
        raise ValueError("the program's dense block is SwiGLU without "
                         "attention biases")
    return ModelConfig(
        name=spec["name"], family=spec["family"],
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        qk_norm=True, rope_theta=float(spec["rope_theta"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]), mlp_act="swiglu")


def native_op(spec: dict) -> int:
    return spec["num_hidden_layers"]


def tokens(spec: dict, shape, rng: np.random.Generator) -> np.ndarray:
    """Zipf-like ids over the vocabulary slice."""
    V = spec["vocab_size"]
    p = 1.0 / np.arange(1, V + 1, dtype=np.float64)
    return rng.choice(V, size=shape, p=p / p.sum()).astype(np.int32)


def _rows(spec: dict, n: int, seq: int, rng) -> dict:
    r = tokens(spec, (n, seq + 1), rng)
    return {"tokens": r[:, :-1], "labels": r[:, 1:]}


def make_data(spec: dict, mix: dict, seed: int):
    rng = np.random.default_rng(seed)
    K, per, seq = mix["clients"], mix["samples_per_client"], mix["seq"]
    allx = _rows(spec, K * per, seq, rng)
    clients = [{k: v[i * per:(i + 1) * per] for k, v in allx.items()}
               for i in range(K)]
    return clients, _rows(spec, mix["eval_samples"], seq, rng)
