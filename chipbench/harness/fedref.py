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

A round takes one of two paths, with the same arithmetic in the same order.
The stacked path trains each OP group's clients side by side and folds all
K deltas in one scan; it holds about (5K + 2) copies of the weights on the
device (``stacked_bytes``).  The streamed path trains one client at a time
and folds its delta into the sum at once, with its error feedback moved in
from the host and back out; it holds about four copies whatever K is.  The
stacked path is taken wherever its footprint fits the device's
``bytes_limit`` (``choose``).

The model is the configuration's reference module (``loss``, ``init``).
Imports nothing of the program."""
from __future__ import annotations

import functools
import json
import sys
from typing import Callable, Dict, List, NamedTuple, Optional

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


def stacked_bytes(n: int, K: int, itemsize: int) -> int:
    """Device bytes of the stacked round at its peak, activations aside:
    the error rows, every client's final weights, the concatenated deltas
    and the scanned new error rows (K rows of ``n`` each), one OP group's
    broadcast weights and gradients (up to K rows more), the global weights
    and the fold's sum."""
    return (5 * K + 2) * n * itemsize


def device_bytes_limit() -> Optional[int]:
    """What the device may hold, or None where the backend does not say
    (the CPU)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def choose(footprint: int, limit: Optional[int]) -> str:
    """The stacked path wherever its footprint fits under the limit."""
    return "streamed" if limit is not None and footprint > limit \
        else "stacked"


class Programs(NamedTuple):
    local: Callable      # op -> jitted step of an OP group, vmapped
    server: Callable     # the stacked round's fold of all K deltas
    step: Callable       # op -> jitted step of one client, ``p`` donated
    fold: Callable       # one client's delta into the sum
    add: Callable        # the sum into the global weights


@functools.lru_cache(maxsize=8)
def _programs(ref, spec_json: str, lr: float, density: float,
              quant_cut: bool, quant_delta: bool, dtype_name: str):
    """The jitted programs of one reference, both paths' (``Programs``),
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

    def fold_one(acc, d, e, wi):
        """A delta into the sum: its error feedback ``e`` (None: none kept)
        added, the top ``density`` kept, sent as int8; returns the new sum
        and what was not sent."""
        carried = d if e is None else jax.tree_util.tree_map(jnp.add, d, e)
        comp = jax.tree_util.tree_map(
            lambda c: reflib.topk_blocks(c, density), carried) \
            if density < 1.0 else carried
        sent = jax.tree_util.tree_map(reflib.int8_blocks, comp) \
            if quant_delta else comp
        acc = jax.tree_util.tree_map(
            lambda a, s: (a + wi * s).astype(dtype), acc, sent)
        return acc, jax.tree_util.tree_map(jnp.subtract, carried, sent)

    @jax.jit
    def server(g, finals, err, w):
        """``finals``: each OP group's stacked client weights, in the order
        the server folds them; ``err`` the error feedback in that order."""
        deltas = jax.tree_util.tree_map(
            lambda gl, *fs: jnp.concatenate(fs) - gl[None], g, *finals)

        def one(acc, xs):
            return fold_one(acc, *xs)
        zero = jax.tree_util.tree_map(jnp.zeros_like, g)
        acc, new_err = jax.lax.scan(one, zero, (deltas, err, w))
        return jax.tree_util.tree_map(jnp.add, g, acc), new_err

    @functools.lru_cache(maxsize=None)
    def step(op: int):
        return jax.jit(lambda p, b: sgd(p, b, op), donate_argnums=0)

    def fold_client(acc, p, g, e, wi):
        acc, rest = fold_one(acc, jax.tree_util.tree_map(jnp.subtract, p, g),
                             e, wi)
        return acc, (rest if keep_err else None)

    # the new error row takes the client's weights' place
    keep_err = density < 1.0 or quant_delta
    fold = jax.jit(fold_client,
                   donate_argnums=(0, 1) if keep_err else (0,))
    add = jax.jit(lambda g, acc: jax.tree_util.tree_map(jnp.add, g, acc),
                  donate_argnums=0)
    return Programs(local, server, step, fold, add)


class Reference(NamedTuple):
    weights: List    # global weights after rounds 0..rounds, float32 host
    err: object      # error feedback after the last round, row i for the
                     # i-th client folded: a (K, ...) device tree (stacked),
                     # a list of float32 host trees or None where no rows
                     # are kept (streamed)
    path: str        # "stacked" or "streamed"


def run(ref, spec: dict, mix: dict, clients: List[dict], ops: List[int],
        seed: int, rounds: int = 3, dtype=jnp.float32,
        precision: Optional[str] = "highest",
        batch_fn: Optional[Callable] = None,
        limit: Optional[int] = None) -> Reference:
    """The reference's first ``rounds`` rounds from the seed.  ``dtype`` is
    the type the whole computation runs in (float32 for the reference,
    bfloat16 for the precision control); ``batch_fn`` may rewrite each batch
    before the step (a planted fault); ``limit`` is the device bytes the
    round may take, the device's own ``bytes_limit`` where None."""
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

    init_key = jax.random.PRNGKey(seed)
    n = sum(a.size for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: ref.init(spec, init_key))))
    footprint = stacked_bytes(n, K, jnp.dtype(dtype).itemsize)
    limit = device_bytes_limit() if limit is None else limit
    path = choose(footprint, limit)
    print(f"fedref: {path} round ({K} clients, {n} weights; stacked "
          f"footprint {footprint} B, limit {limit} B)", file=sys.stderr,
          flush=True)
    progs = _programs(ref, json.dumps(spec, sort_keys=True), lr, density,
                      quant_cut, quant_delta, jnp.dtype(dtype).name)

    def draw(k: int, r: int, it: int) -> Dict[str, np.ndarray]:
        b = streams[k].next()
        if "images" in b and mix["augment"]:
            b["images"] = flip(b["images"], seed, r, k, it)
        return b if batch_fn is None else batch_fn(b)

    def put(b: Dict[str, np.ndarray]) -> dict:
        batch = {key: jnp.asarray(v) for key, v in b.items()}
        if "images" in batch:
            batch["images"] = batch["images"].astype(dtype)
        return batch

    def client(g, acc, err, i: int, r: int):
        """The ``i``-th client folded: its local steps from ``g``, then its
        delta into ``acc``, its error row moved in and back out."""
        k = order[i]
        p = jax.tree_util.tree_map(jnp.copy, g)
        for it in range(iters):
            p = progs.step(ops[k])(p, put(draw(k, r, it)))
        e = None if err is None else jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, dtype), err[i])
        acc, rest = progs.fold(acc, p, g, e, w[i])
        if err is not None:
            err[i] = jax.tree_util.tree_map(
                lambda a: np.asarray(a, np.float32), jax.device_get(rest))
        return acc

    with jax.default_matmul_precision(precision):
        g = _cast(ref.init(spec, init_key), dtype)
        w = jnp.asarray(w, dtype)
        out = [jax.device_get(_cast(g, jnp.float32))]
        if path == "stacked":
            err = jax.tree_util.tree_map(
                lambda a: jnp.zeros((K,) + a.shape, dtype), g)
        elif density < 1.0 or quant_delta:
            err = [jax.tree_util.tree_map(
                lambda a: np.zeros(a.shape, np.float32), g) for _ in order]
        else:
            err = None
        for r in range(rounds):
            if path == "stacked":
                finals = []
                for op in groups:
                    ks = members[op]
                    p = jax.tree_util.tree_map(
                        lambda a: jnp.broadcast_to(a, (len(ks),) + a.shape),
                        g)
                    for it in range(iters):
                        draws = [draw(k, r, it) for k in ks]
                        p = progs.local(op)(p, put(
                            {key: np.stack([b[key] for b in draws])
                             for key in draws[0]}))
                    finals.append(p)
                g, err = progs.server(g, tuple(finals), err, w)
            else:
                acc = jax.tree_util.tree_map(jnp.zeros_like, g)
                for i in range(K):
                    acc = client(g, acc, err, i, r)
                g = progs.add(g, acc)
            out.append(jax.device_get(_cast(g, jnp.float32)))
    return Reference(out, err, path)
