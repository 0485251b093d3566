"""Per-client batching with deterministic shuffling (resumable: the loader
state is just (epoch, cursor), checkpointed alongside the model) and seeded
non-IID client partitioning.

Three pieces:

* ``ClientLoader`` — one client's stream.  Batch order is a pure function of
  ``(seed, epoch, cursor)``, so fast-forwarding ``n`` draws (``skip``)
  reproduces an uninterrupted run bitwise (the resume drill in
  tests/test_runtime.py).
* ``FleetLoader`` — a fleet of per-client streams behind one handle.
  ``next_batches(k_indices)`` draws the *next* batch of each listed client
  and stacks them into ``(G, B, ...)`` arrays for the batched fleet engine
  (fl/fleet.py).  Each client's stream is the same ``ClientLoader`` stream
  the sequential engine would draw — grouping clients differently across
  rounds never changes what any single client sees, and ``state/restore``
  keeps the bitwise-resume guarantee at fleet granularity.
  ``next_indices(k)`` makes the same draw as rows of client ``k``'s
  dataset, for an engine that keeps the data on the device.
* ``dirichlet_partition`` — seeded Dirichlet(α) label-skew split of one
  dataset into K client shards (the standard non-IID benchmark protocol;
  see e.g. Hsu et al. and the heterogeneity survey arXiv:2307.09182).
  Deterministic per ``(seed, K, α)`` and an *exact cover*: every sample
  lands on exactly one client.  The shards are plain dict datasets, so the
  resumable loaders above work on them unchanged.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np


def dirichlet_indices(labels: np.ndarray, num_clients: int, alpha: float,
                      seed: int = 0, min_per_client: int = 1,
                      ) -> List[np.ndarray]:
    """Seeded Dirichlet(α) label-skew partition: per-client sample indices.

    For each class ``c`` the class's samples are split across the ``K``
    clients in proportions ``p ~ Dirichlet(α·1_K)`` (fresh draw per class).
    Small ``α`` → extreme skew (each client sees few classes); ``α → ∞`` →
    IID.  Guarantees:

    * **Exact cover** — the returned index arrays are disjoint and their
      union is ``arange(len(labels))`` (property-tested in
      tests/test_property.py).
    * **Deterministic** — a pure function of ``(labels, K, α, seed)``; no
      global RNG state is read or written.
    * **Non-empty clients** — a deterministic rebalance moves samples from
      the largest shard until every client has ≥ ``min_per_client``
      (a client with zero samples would crash its ``ClientLoader``).
    """
    if num_clients < 1:
        raise ValueError(f"num_clients={num_clients} must be >= 1")
    if alpha <= 0:
        raise ValueError(f"alpha={alpha} must be > 0 (Dirichlet "
                         f"concentration)")
    labels = np.asarray(labels)
    if labels.ndim > 1:
        # token-style (N, T) targets: key the skew on each sequence's first
        # target so sequence datasets partition too (still an exact cover)
        labels = labels.reshape(len(labels), -1)[:, 0]
    n = len(labels)
    if n < num_clients * min_per_client:
        raise ValueError(
            f"{n} samples cannot give {num_clients} clients "
            f">= {min_per_client} each")
    rng = np.random.RandomState(seed)
    shards: List[List[np.ndarray]] = [[] for _ in range(num_clients)]
    for c in np.unique(labels):
        idx = np.flatnonzero(labels == c)
        rng.shuffle(idx)
        p = rng.dirichlet(np.full(num_clients, float(alpha)))
        # exact integer counts summing to len(idx): floor + largest-remainder
        raw = p * len(idx)
        counts = np.floor(raw).astype(np.int64)
        rem = len(idx) - int(counts.sum())
        if rem:
            order = np.argsort(-(raw - counts), kind="stable")
            counts[order[:rem]] += 1
        stops = np.cumsum(counts)
        start = 0
        for k, stop in enumerate(stops):
            if stop > start:
                shards[k].append(idx[start:stop])
            start = int(stop)
    parts = [np.sort(np.concatenate(s)) if s
             else np.empty(0, np.int64) for s in shards]
    # deterministic rebalance: donate from the largest shard to any shard
    # below the floor (ties broken by client index via argmax/argmin)
    sizes = np.asarray([len(p) for p in parts])
    while sizes.min() < min_per_client:
        src = int(np.argmax(sizes))
        dst = int(np.argmin(sizes))
        need = min_per_client - sizes[dst]
        give = min(need, sizes[src] - min_per_client)
        if give <= 0:
            raise ValueError("rebalance stuck: not enough samples to give "
                             f"every client >= {min_per_client}")
        moved, parts[src] = parts[src][-give:], parts[src][:-give]
        parts[dst] = np.sort(np.concatenate([parts[dst], moved]))
        sizes[src] -= give
        sizes[dst] += give
    return parts


def dirichlet_partition(data: Dict[str, np.ndarray], num_clients: int,
                        alpha: float, seed: int = 0,
                        label_key: str = "labels",
                        min_per_client: int = 1,
                        ) -> List[Dict[str, np.ndarray]]:
    """Split one dict dataset into K Dirichlet(α) label-skewed client shards.

    Every array in ``data`` is indexed by the same per-client index sets
    (from ``dirichlet_indices`` over ``data[label_key]``), so arbitrary
    extra keys (images, tokens, ...) ride along.  Drop-in replacement for
    the IID ``data.synthetic.split_clients``.
    """
    parts = dirichlet_indices(data[label_key], num_clients, alpha,
                              seed=seed, min_per_client=min_per_client)
    return [{k: v[idx] for k, v in data.items()} for idx in parts]


class ClientLoader:
    def __init__(self, data: Dict[str, np.ndarray], batch_size: int,
                 seed: int = 0):
        self.data = data
        self.n = len(next(iter(data.values())))
        self.batch_size = min(batch_size, self.n)
        self.seed = seed
        self.epoch = 0
        self.cursor = 0
        self._perm = self._permutation(0)

    def _permutation(self, epoch: int) -> np.ndarray:
        return np.random.RandomState(self.seed + epoch).permutation(self.n)

    def state(self) -> Tuple[int, int]:
        return (self.epoch, self.cursor)

    def restore(self, state: Tuple[int, int]):
        self.epoch, self.cursor = state
        self._perm = self._permutation(self.epoch)

    def next_indices(self) -> np.ndarray:
        """Rows of ``data`` in the next batch; advances ``(epoch, cursor)``
        exactly as ``next_batch`` does, which draws through it."""
        if self.cursor + self.batch_size > self.n:
            self.epoch += 1
            self.cursor = 0
            self._perm = self._permutation(self.epoch)
        idx = self._perm[self.cursor:self.cursor + self.batch_size]
        self.cursor += self.batch_size
        return idx

    def next_batch(self) -> Dict[str, np.ndarray]:
        idx = self.next_indices()
        return {k: v[idx] for k, v in self.data.items()}

    def skip(self, n: int):
        """Fast-forward ``n`` draws without materializing the batches."""
        for _ in range(n):
            if self.cursor + self.batch_size > self.n:
                self.epoch += 1
                self.cursor = 0
            self.cursor += self.batch_size
        self._perm = self._permutation(self.epoch)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next_batch()


class FleetLoader:
    """K deterministic per-client streams behind one batched handle.

    Client streams are materialized *lazily*: ``for_clients`` records the
    fleet description and builds each ``ClientLoader`` on first use, so a
    million-client registered fleet with a sampled cohort (fl/cohort.py)
    only ever instantiates the clients that actually train —
    ``materialized`` counts them, and benchmarks/hierarchy.py asserts the
    bound.  An untouched client's stream state is the initial ``(epoch=0,
    cursor=0)``, so ``state``/``restore`` keep the bitwise-resume guarantee
    without forcing materialization: restoring the initial state is a
    no-op.  Each materialized stream is the same ``ClientLoader(seed + k)``
    stream the eager loader always built — laziness never changes what any
    client sees.
    """

    def __init__(self, loaders: Sequence[ClientLoader]):
        # eager construction (back-compat): validate batch uniformity now
        self._loaders: Dict[int, ClientLoader] = dict(enumerate(loaders))
        self._K = len(self._loaders)
        self._data: Optional[Sequence[Dict[str, np.ndarray]]] = None
        self._batch_size = None
        self._seed = 0
        sizes = {ld.batch_size for ld in self._loaders.values()}
        if len(sizes) > 1:
            raise ValueError(
                f"FleetLoader needs a uniform batch size to stack clients; "
                f"got {sorted(sizes)} (some client datasets are smaller than "
                f"the requested batch size)")
        self._bs_seen = sizes.pop() if sizes else None

    @classmethod
    def for_clients(cls, clients_data: Sequence[Dict[str, np.ndarray]],
                    batch_size: int, seed: int = 0) -> "FleetLoader":
        """One lazy ``ClientLoader(seed + k)`` per client — the exact
        streams the sequential federated loop has always used, built on
        first draw."""
        self = cls.__new__(cls)
        self._loaders = {}
        self._K = len(clients_data)
        self._data = clients_data
        self._batch_size = batch_size
        self._seed = seed
        # the eager constructor's uniform-batch contract, checked upfront
        # from dataset lengths alone — no stream is materialized (building
        # a ClientLoader costs a seeded permutation per client; a len() is
        # free even at K=1M)
        sizes = {min(batch_size, len(next(iter(d.values()))))
                 for d in clients_data}
        if len(sizes) > 1:
            raise ValueError(
                f"FleetLoader needs a uniform batch size to stack clients; "
                f"got {sorted(sizes)} (some client datasets are smaller than "
                f"the requested batch size)")
        self._bs_seen = sizes.pop() if sizes else None
        return self

    def _get(self, k: int) -> ClientLoader:
        ld = self._loaders.get(k)
        if ld is None:
            if self._data is None:
                raise IndexError(f"client {k} outside eager fleet")
            ld = ClientLoader(self._data[k], self._batch_size,
                              seed=self._seed + k)
            # the uniform-batch check the eager path does upfront, applied
            # at materialization time (the first mismatching client raises)
            if self._bs_seen is None:
                self._bs_seen = ld.batch_size
            elif ld.batch_size != self._bs_seen:
                raise ValueError(
                    f"FleetLoader needs a uniform batch size to stack "
                    f"clients; got {sorted({self._bs_seen, ld.batch_size})} "
                    f"(some client datasets are smaller than the requested "
                    f"batch size)")
            self._loaders[k] = ld
        return ld

    @property
    def loaders(self) -> List[ClientLoader]:
        """All K streams as a list — materializes the whole fleet (the
        eager legacy view; prefer per-client access at fleet scale)."""
        return [self._get(k) for k in range(self._K)]

    @property
    def materialized(self) -> int:
        """How many client streams have actually been instantiated."""
        return len(self._loaders)

    def __len__(self) -> int:
        return self._K

    @property
    def datasets(self) -> List[Dict[str, np.ndarray]]:
        """Every client's dataset in client order, without building any
        stream (the batched engine's device-resident slab reads them)."""
        if self._data is not None:
            return list(self._data)
        return [self._loaders[k].data for k in range(self._K)]

    def next_batch(self, k: int) -> Dict[str, np.ndarray]:
        """Client ``k``'s next batch (the sequential engine's draw)."""
        return self._get(k).next_batch()

    def next_indices(self, k: int) -> np.ndarray:
        """Rows of client ``k``'s dataset in its next batch: the same draw,
        and the same stream advance, as ``next_batch(k)``."""
        return self._get(k).next_indices()

    def next_batches(self, k_indices: Sequence[int],
                     pad_to: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Draw the next batch of every listed client, stacked ``(G, B, ...)``
        in ``k_indices`` order.  Each client advances exactly one draw.

        ``pad_to`` (>= len(k_indices)) appends repeat copies of the *first*
        listed client's draw until the stack has that many rows — without
        advancing any stream.  The batched fleet engine uses this to keep
        chunk shapes stable across rounds and divisible by the mesh ``data``
        axis (``parallel.sharding.client_chunk_pad``); the padding rows are
        dropped from the engine's output before aggregation, so they never
        carry weight."""
        batches = [self._get(k).next_batch() for k in k_indices]
        if pad_to is not None and pad_to > len(batches):
            batches = batches + [batches[0]] * (pad_to - len(batches))
        return {key: np.stack([b[key] for b in batches])
                for key in batches[0]}

    def skip(self, n: int):
        """Fast-forward every client stream ``n`` draws (legacy resume;
        materializes the fleet — cohort-aware resume uses
        ``skip_client``)."""
        for k in range(self._K):
            self._get(k).skip(n)

    def skip_client(self, k: int, n: int):
        """Fast-forward one client's stream ``n`` draws (cohort-aware
        resume: only clients that ever trained need touching)."""
        if n:
            self._get(k).skip(n)

    def state(self) -> List[Tuple[int, int]]:
        """Per-client ``(epoch, cursor)``; unmaterialized streams report
        the initial ``(0, 0)`` without being built."""
        return [self._loaders[k].state() if k in self._loaders else (0, 0)
                for k in range(self._K)]

    def restore(self, states: Sequence[Tuple[int, int]]):
        if len(states) != self._K:
            raise ValueError(
                f"fleet state has {len(states)} client streams, loader has "
                f"{self._K} — refusing a partial restore that "
                f"would silently break bitwise resume")
        for k, st in enumerate(states):
            if tuple(st) != (0, 0) or k in self._loaders:
                self._get(k).restore(tuple(st))
