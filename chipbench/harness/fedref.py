"""Plain reference of the first federated rounds of a training cell.

Each round, every client starts from the global weights and runs
``local_iters`` steps of SGD on its own batches through its offloading cut
(int8 across the cut when the mix says so).  Its batches follow the
configuration's data order: client ``k`` draws batches in the order of a
permutation seeded ``seed + k + epoch``, a new epoch when the next batch
would run past its data; images are flipped left-right with probability
1/2, seeded by ``(seed, round, client, iteration)``.  The server then takes
each client's delta, adds the client's error feedback, keeps the top
``density`` of every block, sends it as int8, keeps what was not sent as
the new error feedback, and adds the sample-weighted mean of what was sent
to the global weights (``reflib``).

The model is the configuration's reference module (``loss``, ``init``).
Imports nothing of the program."""
from __future__ import annotations

import functools
import json
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import reflib


def flip(images: np.ndarray, seed: int, r: int, k: int, it: int
         ) -> np.ndarray:
    rng = np.random.RandomState(
        (seed * 1_000_003 + r * 1009 + k * 31 + it) % (2 ** 31))
    f = rng.rand(len(images)) < 0.5
    return np.where(f[:, None, None, None], images[:, :, ::-1, :], images)


class ClientStream:
    """One client's batches in the configuration's order."""

    def __init__(self, data: Dict[str, np.ndarray], batch: int, seed: int):
        self.data, self.seed = data, seed
        self.n = len(next(iter(data.values())))
        self.batch = min(batch, self.n)
        self.epoch, self.cursor = 0, 0

    def next(self) -> Dict[str, np.ndarray]:
        if self.cursor + self.batch > self.n:
            self.epoch, self.cursor = self.epoch + 1, 0
        perm = np.random.RandomState(self.seed + self.epoch).permutation(
            self.n)
        idx = perm[self.cursor:self.cursor + self.batch]
        self.cursor += self.batch
        return {k: v[idx] for k, v in self.data.items()}


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


@functools.lru_cache(maxsize=8)
def _programs(ref, spec_json: str, lr: float, density: float,
              quant_cut: bool, quant_delta: bool, dtype_name: str):
    """The jitted local step (per OP) and server step of one reference,
    built once per process and reused by every run with the same setting."""
    spec, dtype = json.loads(spec_json), jnp.dtype(dtype_name)

    def sgd(p, batch, op):
        grads = jax.grad(lambda q: ref.loss(spec, q, batch, op,
                                            quant_cut))(p)
        return jax.tree_util.tree_map(
            lambda a, b: (a - lr * b).astype(dtype), p, grads)

    @functools.lru_cache(maxsize=None)
    def local(op: int):
        return jax.jit(jax.vmap(lambda p, b: sgd(p, b, op)))

    @jax.jit
    def server(g, finals, err, w):
        """``finals``: each OP group's stacked client weights, in the order
        the server folds them; ``err`` the error feedback in that order."""
        deltas = jax.tree_util.tree_map(
            lambda gl, *fs: jnp.concatenate(fs) - gl[None], g, *finals)

        def one(acc, xs):
            d, e, wi = xs
            carried = jax.tree_util.tree_map(jnp.add, d, e)
            comp = jax.tree_util.tree_map(
                lambda c: reflib.topk_blocks(c, density), carried) \
                if density < 1.0 else carried
            sent = jax.tree_util.tree_map(reflib.int8_blocks, comp) \
                if quant_delta else comp
            acc = jax.tree_util.tree_map(
                lambda a, s: (a + wi * s).astype(dtype), acc, sent)
            return acc, jax.tree_util.tree_map(jnp.subtract, carried, sent)
        zero = jax.tree_util.tree_map(jnp.zeros_like, g)
        acc, new_err = jax.lax.scan(one, zero, (deltas, err, w))
        return jax.tree_util.tree_map(jnp.add, g, acc), new_err

    return local, server


def run(ref, spec: dict, mix: dict, clients: List[dict], ops: List[int],
        seed: int, rounds: int = 3, dtype=jnp.float32,
        precision: Optional[str] = "highest",
        batch_fn: Optional[Callable] = None) -> List:
    """Global weights after rounds 0..``rounds`` (index 0 is the init), as
    float32 host arrays.  ``dtype`` is the type the whole computation runs
    in (float32 for the reference, bfloat16 for the precision control);
    ``batch_fn`` may rewrite each batch before the step (a planted fault)."""
    K, iters = len(clients), mix["local_iters"]
    lr, density = mix["lr"], mix["delta_density"]
    quant_cut, quant_delta = mix["quantize_transfer"], mix["quantize_deltas"]
    streams = [ClientStream(c, mix["batch"], seed + k)
               for k, c in enumerate(clients)]
    sizes = np.asarray([len(c["labels"]) for c in clients], np.float64)
    weights = sizes / sizes.sum()
    groups = list(dict.fromkeys(ops))
    members = {op: [k for k in range(K) if ops[k] == op] for op in groups}
    # the server folds clients grouped by OP in order of first appearance,
    # ascending client index within a group; the error feedback is kept in
    # that order
    order = [k for op in groups for k in members[op]]
    w = weights[order]

    local, server = _programs(ref, json.dumps(spec, sort_keys=True), lr,
                              density, quant_cut, quant_delta,
                              jnp.dtype(dtype).name)
    with jax.default_matmul_precision(precision):
        g = _cast(ref.init(spec, jax.random.PRNGKey(seed)), dtype)
        err = jax.tree_util.tree_map(
            lambda a: jnp.zeros((K,) + a.shape, dtype), g)
        w = jnp.asarray(w, dtype)
        out = [jax.device_get(_cast(g, jnp.float32))]
        for r in range(rounds):
            finals = []
            for op in groups:
                ks = members[op]
                p = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (len(ks),) + a.shape), g)
                for it in range(iters):
                    draws = [streams[k].next() for k in ks]
                    if "images" in draws[0] and mix["augment"]:
                        for k, b in zip(ks, draws):
                            b["images"] = flip(b["images"], seed, r, k, it)
                    if batch_fn is not None:
                        draws = [batch_fn(b) for b in draws]
                    batch = {key: jnp.asarray(np.stack([b[key] for b in draws]))
                             for key in draws[0]}
                    if "images" in batch:
                        batch["images"] = batch["images"].astype(dtype)
                    p = local(op)(p, batch)
                finals.append(p)
            g, err = server(g, tuple(finals), err, w)
            out.append(jax.device_get(_cast(g, jnp.float32)))
    return out
