"""vgg5: the program's configuration and the cell's data, from vgg5.json.

``program_config`` builds the ``VGGConfig`` the program runs; ``make_data``
makes every client's CIFAR-shaped images and the eval set from a seed, in
bulk on the host (the program's loaders index numpy arrays)."""
from __future__ import annotations

import numpy as np


def program_config(spec: dict):
    from repro.configs.vgg import VGGConfig
    return VGGConfig(name=spec["name"], layers=tuple(spec["layers"]),
                     ops=tuple(spec["offloading_points"]),
                     input_hw=spec["input_hw"], input_ch=spec["input_ch"],
                     num_classes=spec["num_classes"])


def native_op(spec: dict) -> int:
    return len(spec["layers"])


def _images(spec: dict, n: int, rng: np.random.Generator) -> dict:
    hw, ch, nc = spec["input_hw"], spec["input_ch"], spec["num_classes"]
    labels = rng.integers(0, nc, size=n).astype(np.int32)
    yy, xx = np.meshgrid(np.linspace(0, 1, hw), np.linspace(0, 1, hw),
                         indexing="ij")
    templates = np.stack([
        np.stack([np.sin(2 * np.pi * ((c + 1) * xx + k))
                  * np.cos(2 * np.pi * ((c % 3 + 1) * yy - k))
                  for k in range(ch)], axis=-1)
        for c in range(nc)]).astype(np.float32)
    images = templates[labels]
    images += 0.8 * rng.standard_normal(images.shape, dtype=np.float32)
    return {"images": images, "labels": labels}


def make_data(spec: dict, mix: dict, seed: int):
    """``clients`` dicts of ``samples_per_client`` images each, and an eval
    set of ``eval_samples``."""
    rng = np.random.default_rng(seed)
    K, per = mix["clients"], mix["samples_per_client"]
    allx = _images(spec, K * per, rng)
    clients = [{k: v[i * per:(i + 1) * per] for k, v in allx.items()}
               for i in range(K)]
    return clients, _images(spec, mix["eval_samples"], rng)
