"""Fleet execution engines: how one round of local training actually runs.

``run_federated`` (fl/loop.py) plans per-device Offloading Points and
aggregates deltas; *this* module owns the step in between — K clients each
running ``local_iters`` SGD iterations from the same global params.  Two
interchangeable engines implement it (``FLConfig.engine``):

* ``SequentialEngine`` — the literal reading of the paper's testbed: a
  Python loop over clients, one jit dispatch per local iteration.  Faithful
  but O(K x local_iters) dispatches per round, which caps simulation
  throughput at a handful of clients.
* ``BatchedEngine`` — the fleet-scale path.  Clients are grouped by their
  planned OP (the only static argument of the compiled step) and chunked to
  ``max_group``; each chunk trains as a single ``jax.vmap`` over clients of
  a ``jax.lax.scan`` over local iterations — K/max_group dispatches per
  round instead of K x local_iters, one compile per (config, OP, chunk
  size).  Per-client batch streams, shuffling and the
  horizontal-flip augmentation RNG are bitwise identical to the sequential
  engine (batches are materialized host-side via
  ``data.loader.FleetLoader.next_batches`` and stacked ``(G, I, B, ...)``),
  so the same seed yields the same history up to float32 summation order
  (drilled in tests/test_fleet.py).

With ``FLConfig.mesh_shape`` set, the batched engine goes *mesh-parallel*
(``make_sharded_fleet_step``): each chunk's client axis splits along the
mesh ``data`` axis under an explicit ``shard_map`` — chunks pad to
shard-divisible sizes, stacked draws land pre-placed, and every device
trains its own slice of the clients with zero collectives.  The sharded
per-client rows gather to one device for the row glue and re-land on the
mesh as the ``ShardedServerStep``'s delta matrix, so one round runs local
training, compression, aggregation and apply across all devices
(tests/test_mesh_fleet.py pins the equivalence contract;
benchmarks/fleet_scaling.py the 1-dev vs 8-dev round-time curve).

Both engines return ``(idxs, rows)``: the trained client indices and their
post-round parameters — a list of pytrees (sequential) or one pytree with a
leading client axis (batched).  ``rows_as_list`` / ``take_rows`` adapt
either form for the aggregation paths: the fused flat-buffer server step
(fl/flatbuf.py, the default) stacks rows straight into its ``(K, n)``
delta matrix via ``FlatLayout.rows_to_deltas``, the reference per-leaf
path consumes the per-client list.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.loader import FleetLoader
from repro.models.split_program import SplitProgram

Params = Any


def flip_augment(images: np.ndarray, seed: int, round_idx: int, client: int,
                 it: int) -> np.ndarray:
    """Horizontal flip with p=0.5 (paper §V-B), keyed by
    ``(seed, round, client, iter)`` so any engine — and any resumed run —
    reproduces the exact augmentation stream."""
    rng = np.random.RandomState(
        (seed * 1_000_003 + round_idx * 1009 + client * 31 + it) % (2 ** 31))
    flip = rng.rand(len(images)) < 0.5
    return np.where(flip[:, None, None, None], images[:, :, ::-1, :], images)


def _sgd_update(program: SplitProgram, quantize: bool, params, batch, lr, op):
    loss, grads = jax.value_and_grad(
        lambda p: program.loss_through_cut(p, batch, op,
                                           quantize=quantize))(params)
    new = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return new, loss


def make_local_step(program: SplitProgram, quantize: bool):
    """One client, one iteration (the sequential engine's unit of work)."""

    @partial(jax.jit, static_argnames=("op",))
    def step(params, batch, lr, op):
        return _sgd_update(program, quantize, params, batch, lr, op)

    return step


def make_local_step_masked(program: SplitProgram, quantize: bool):
    """Width-masked client iteration (HeteroFL, fl/hetero.py): the update
    is ``p - lr * (mask * grad)`` — a client that started from
    ``mask * global`` never leaves its subnetwork, so its delta vs the
    global is confined to the coordinates it actually trained (after the
    server re-masks; see ServerStep's coverage-count aggregation)."""

    @partial(jax.jit, static_argnames=("op",))
    def step(params, mask, batch, lr, op):
        loss, grads = jax.value_and_grad(
            lambda p: program.loss_through_cut(p, batch, op,
                                               quantize=quantize))(params)
        new = jax.tree_util.tree_map(lambda p, g, m: p - lr * (m * g),
                                     params, grads, mask)
        return new, loss

    return step


def make_fleet_step(program: SplitProgram, quantize: bool):
    """One OP group, one round: vmap over the client axis of a lax.scan over
    local iterations.  ``batches`` leaves are ``(G, I, B, ...)``; ``params``
    is the *unstacked* global pytree (every client starts the round from it,
    so vmap broadcasts with ``in_axes=None``).  Returns per-client final
    params stacked ``(G, ...)`` and per-(client, iter) losses ``(G, I)``."""

    @partial(jax.jit, static_argnames=("op",))
    def fleet_step(params, batches, lr, op):
        def one_client(p, client_batches):       # leaves (I, B, ...)
            def body(p, batch):
                return _sgd_update(program, quantize, p, batch, lr, op)
            return jax.lax.scan(body, p, client_batches)

        return jax.vmap(one_client, in_axes=(None, 0))(params, batches)

    return fleet_step


def make_fleet_step_masked(program: SplitProgram, quantize: bool):
    """Width-masked OP-group round (HeteroFL): every client in the group
    shares one ``mask`` (the batched engine groups by ``(OP, width)``), so
    the mask broadcasts like the params — start from ``mask * global``,
    apply ``mask * grad`` updates, vmap over the client axis."""

    @partial(jax.jit, static_argnames=("op",))
    def fleet_step(params, mask, batches, lr, op):
        def one_client(p, client_batches):       # leaves (I, B, ...)
            def body(p, batch):
                loss, grads = jax.value_and_grad(
                    lambda q: program.loss_through_cut(
                        q, batch, op, quantize=quantize))(p)
                new = jax.tree_util.tree_map(
                    lambda q, g, m: q - lr * (m * g), p, grads, mask)
                return new, loss
            return jax.lax.scan(body, p, client_batches)

        p0 = jax.tree_util.tree_map(jnp.multiply, mask, params)
        return jax.vmap(one_client, in_axes=(None, 0))(p0, batches)

    return fleet_step


def make_sharded_fleet_step(program: SplitProgram, quantize: bool, mesh):
    """Mesh-parallel OP-group round: the same vmap-of-scan body, wrapped in
    an explicit ``shard_map`` that splits the stacked client axis along the
    mesh ``data`` axis — each device trains ``G / data`` clients with the
    plain per-device program, and because clients are independent the body
    needs ZERO collectives (``check_vma=False``: outputs are client-sharded
    by construction).

    Explicit ``shard_map``, not GSPMD propagation, on purpose: letting the
    partitioner chew through the vmap-of-scan training step inserts
    pathological collectives on the CPU backend (measured ~8x *slower* than
    single-device for the conv family), while the shard_map body compiles to
    exactly the legacy program per shard.  For conv families this is also
    where the mesh *wins* on CPU: XLA CPU lowers the client-batched conv
    backward to grouped convolutions that scale superlinearly in the client
    axis, so 8 shards of ``G=1`` beat one fused ``G=8`` even when the host
    serializes the shards (benchmarks/fleet_scaling.py records the curve).

    ``params`` (and ``lr``) use replicated in_specs: the jit wrapper gathers
    a tp-placed global (``SplitProgram.shard_params``) once per dispatch —
    clients all start from the same full params, so model-axis devices hold
    replicas inside the step and the ``model`` axis keeps its PR 9 role of
    sharding the flat server-step buffer between rounds.  ``batches`` must
    arrive with the client axis a multiple of the ``data`` size
    (``parallel.sharding.client_chunk_pad``) and placed by
    ``SplitProgram.shard_batches``."""
    from jax.sharding import PartitionSpec as P

    @partial(jax.jit, static_argnames=("op",))
    def fleet_step(params, batches, lr, op):
        def body(params, batches, lr):
            def one_client(p, client_batches):
                def step(p, batch):
                    return _sgd_update(program, quantize, p, batch, lr, op)
                return jax.lax.scan(step, p, client_batches)

            return jax.vmap(one_client, in_axes=(None, 0))(params, batches)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(), P("data"), P()),
                             out_specs=P("data"), check_vma=False)(
                             params, batches, lr)

    return fleet_step


def make_sharded_fleet_step_masked(program: SplitProgram, quantize: bool,
                                   mesh):
    """Width-masked (HeteroFL) variant of ``make_sharded_fleet_step``: the
    group-wide mask rides along replicated like the params — every shard
    applies the same subnetwork mask to its slice of the client axis."""
    from jax.sharding import PartitionSpec as P

    @partial(jax.jit, static_argnames=("op",))
    def fleet_step(params, mask, batches, lr, op):
        def body(params, mask, batches, lr):
            def one_client(p, client_batches):
                def step(p, batch):
                    loss, grads = jax.value_and_grad(
                        lambda q: program.loss_through_cut(
                            q, batch, op, quantize=quantize))(p)
                    new = jax.tree_util.tree_map(
                        lambda q, g, m: q - lr * (m * g), p, grads, mask)
                    return new, loss
                return jax.lax.scan(step, p, client_batches)

            p0 = jax.tree_util.tree_map(jnp.multiply, mask, params)
            return jax.vmap(one_client, in_axes=(None, 0))(p0, batches)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(), P(), P("data"), P()),
                             out_specs=P("data"), check_vma=False)(
                             params, mask, batches, lr)

    return fleet_step


class SequentialEngine:
    """One jit dispatch per (client, iteration) — the pre-fleet loop."""

    name = "sequential"

    def __init__(self, program: SplitProgram, local_iters: int, seed: int,
                 augment: bool, quantize: bool, mesh=None):
        # ``mesh`` is accepted for engine-interface uniformity and ignored:
        # the sequential oracle always runs the legacy per-client dispatches
        # (with FLConfig.mesh_shape set it still benefits from the sharded
        # *server* step; only the batched engine shards local training)
        self.local_iters = local_iters
        self.seed = seed
        self.augment = augment
        self._step = make_local_step(program, quantize)
        self._step_masked = make_local_step_masked(program, quantize)

    def run_round(self, params: Params, loader: FleetLoader,
                  ops: Sequence[int], alive_idx: Sequence[int],
                  round_idx: int, lr: float, hetero=None
                  ) -> Tuple[List[int], List[Params]]:
        out: List[Params] = []
        for k in alive_idx:
            if hetero is not None:
                p_k = hetero.apply(params, k)
                mask = hetero.mask_tree(k)
            else:
                p_k = params
            for it in range(self.local_iters):
                batch = loader.next_batch(k)
                if self.augment and "images" in batch:
                    batch["images"] = flip_augment(batch["images"], self.seed,
                                                   round_idx, k, it)
                jbatch = {key: jnp.asarray(v) for key, v in batch.items()}
                if hetero is not None:
                    p_k, _ = self._step_masked(p_k, mask, jbatch,
                                               jnp.float32(lr), int(ops[k]))
                else:
                    p_k, _ = self._step(p_k, jbatch, jnp.float32(lr),
                                        int(ops[k]))
            out.append(p_k)
        return list(alive_idx), out


@dataclasses.dataclass
class StackedRows:
    """Per-client parameters as ONE pytree with a leading ``(K, ...)`` client
    axis on every leaf.  A distinct type (not a bare pytree) because a params
    pytree may itself be a Python list — e.g. VGG's per-layer list — so the
    row container must be distinguishable from a list of client pytrees."""

    tree: Params

    def __len__(self) -> int:
        return int(jax.tree_util.tree_leaves(self.tree)[0].shape[0])


class BatchedEngine:
    """One jit dispatch per (OP group chunk, round): vmap'd clients, scanned
    iterations.  Compiles once per (OP, chunk size) and re-uses the
    executable across rounds.

    ``max_group`` caps the clients fused into one dispatch: the working set
    of a fused group is ~``group x (params + grads + adjoints)``, so an
    unbounded group blows past cache/HBM at large K while the dispatch
    savings have long since saturated.  The default (8) is the measured
    sweet spot on CPU; raise it on accelerators with memory to spare.

    ``mesh`` (a ``(data, model)`` Mesh from ``parallel.sharding
    .make_flat_mesh``, threaded from ``FLConfig.mesh_shape`` by both loops)
    switches every chunk to the mesh-parallel ``shard_map`` fleet step: the
    chunk size rounds up to a multiple of the ``data`` axis (short chunks
    pad with repeated, dropped-after-the-step rows — ``client_chunk_pad``),
    stacked draws are placed shard-wise (``SplitProgram.shard_batches``)
    before dispatch, and each device trains ``chunk / data`` clients.
    Chunk outputs are gathered back to the mesh's first device before the
    row glue (slice/concat/take_rows): eager per-leaf ops on data-sharded
    arrays thrash the CPU backend's collective rendezvous, and the flat
    layout re-places the delta matrix on the mesh for the sharded server
    step anyway (``ShardedFlatLayout.rows_to_deltas``) — same
    compute-sharded / glue-pinned compromise PR 9 pinned for the layout.
    ``mesh=None`` is the exact legacy single-device engine, bitwise
    (tests/test_mesh_fleet.py)."""

    name = "batched"

    def __init__(self, program: SplitProgram, local_iters: int, seed: int,
                 augment: bool, quantize: bool, max_group: int = 8,
                 mesh=None):
        self.program = program
        self.local_iters = local_iters
        self.seed = seed
        self.augment = augment
        self.max_group = max(1, int(max_group))
        self.mesh = mesh
        if mesh is not None:
            if "data" not in mesh.shape:
                raise ValueError(f"mesh axes {tuple(mesh.shape)} must "
                                 f"include 'data' (make_flat_mesh)")
            self.data_size = int(mesh.shape["data"])
            # smallest multiple of the data axis >= max_group, so every
            # full chunk splits evenly across the data-axis devices
            self.chunk = -(-self.max_group // self.data_size) \
                * self.data_size
            self._step = make_sharded_fleet_step(program, quantize, mesh)
            self._step_masked = make_sharded_fleet_step_masked(
                program, quantize, mesh)
            self._home = mesh.devices.flat[0]
        else:
            self.data_size = 1
            self.chunk = self.max_group
            self._step = make_fleet_step(program, quantize)
            self._step_masked = make_fleet_step_masked(program, quantize)

    def _group(self, ops: Sequence[int], alive_idx: Sequence[int],
               hetero=None) -> Dict[tuple, List[int]]:
        """Fusable groups: clients sharing (OP, width) — both change the
        traced computation (OP is a static argument, the width mask an
        operand that must broadcast across the group)."""
        groups: Dict[tuple, List[int]] = {}
        for k in alive_idx:
            width = hetero.width(k) if hetero is not None else 1.0
            groups.setdefault((int(ops[k]), width), []).append(k)
        return groups

    def _stack_round(self, loader: FleetLoader, ks: List[int],
                     round_idx: int, pad_to: Optional[int] = None
                     ) -> Dict[str, jnp.ndarray]:
        """Materialize the group's whole round of data host-side: for each
        local iteration draw every client's next batch (the same per-client
        streams the sequential engine consumes), augment, and stack to
        ``(G, I, B, ...)``.  ``pad_to > len(ks)`` repeats the first client's
        (augmented) rows up to that chunk size — stable compiled shapes and
        shard-divisible client axes — without advancing any stream; on a
        mesh the stack lands shard-wise placed (clients along ``data``)."""
        C = max(len(ks), int(pad_to or 0))
        per_iter: List[Dict[str, np.ndarray]] = []
        for it in range(self.local_iters):
            nb = loader.next_batches(ks, pad_to=C)           # (C, B, ...)
            if self.augment and "images" in nb:
                imgs = np.stack(
                    [flip_augment(nb["images"][i], self.seed, round_idx, k,
                                  it)
                     for i, k in enumerate(ks)])
                if C > len(ks):        # padding rows repeat augmented row 0
                    imgs = np.concatenate(
                        [imgs, np.repeat(imgs[:1], C - len(ks), axis=0)])
                nb["images"] = imgs
            per_iter.append(nb)
        batches = {key: jnp.asarray(np.stack([pb[key] for pb in per_iter],
                                             axis=1))
                   for key in per_iter[0]}
        if self.mesh is not None:
            batches = self.program.shard_batches(batches, self.mesh)
        return batches

    def run_round(self, params: Params, loader: FleetLoader,
                  ops: Sequence[int], alive_idx: Sequence[int],
                  round_idx: int, lr: float, hetero=None
                  ) -> Tuple[List[int], StackedRows]:
        from repro.parallel.sharding import client_chunk_pad
        idxs: List[int] = []
        stacked: List[Params] = []
        for (op, _w), all_ks in self._group(ops, alive_idx, hetero).items():
            for i in range(0, len(all_ks), self.chunk):
                ks = all_ks[i:i + self.chunk]
                # pad a short tail chunk of a multi-chunk group up to the
                # full chunk size (repeating data rows, never drawing extra
                # batches) so chunk sizes — and therefore compiled (G, ...)
                # shapes — don't vary with K % chunk or failure counts; a
                # single-chunk group pads only to the next multiple of the
                # mesh data axis (0 rows on a single device), so per-round
                # membership changes never force a replicate fallback or a
                # recompile on the client axis
                if len(all_ks) > len(ks):
                    pad_to = self.chunk
                else:
                    pad_to = len(ks) + client_chunk_pad(len(ks),
                                                        self.data_size)
                batches = self._stack_round(loader, ks, round_idx,
                                            pad_to=pad_to)
                if hetero is not None:
                    finals, _ = self._step_masked(
                        params, hetero.mask_tree(ks[0]), batches,
                        jnp.float32(lr), op)
                else:
                    finals, _ = self._step(params, batches, jnp.float32(lr),
                                           op)
                if self.mesh is not None:
                    # one gather per chunk off the data axis (pure data
                    # movement, bitwise): the row glue below and the flat
                    # layout's flatten stay on the documented single-device
                    # path, and rows_to_deltas re-places the delta matrix
                    # on the mesh for the sharded server step
                    finals = jax.device_put(finals, self._home)
                if pad_to > len(ks):
                    finals = jax.tree_util.tree_map(lambda a: a[:len(ks)],
                                                    finals)
                idxs.extend(ks)
                stacked.append(finals)
        if not stacked:
            return [], StackedRows(None)
        rows = stacked[0] if len(stacked) == 1 else jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *stacked)
        return idxs, StackedRows(rows)


ENGINES = {"sequential": SequentialEngine, "batched": BatchedEngine}


def get_engine(name: str, program: SplitProgram, local_iters: int, seed: int,
               augment: bool, quantize: bool, mesh=None):
    """Build the configured fleet engine.  ``mesh`` (from
    ``FLConfig.mesh_shape`` via the loops' ``_resolve_mesh``) turns the
    batched engine mesh-parallel; the sequential engine accepts and ignores
    it (it stays the single-device oracle the mesh path is tested
    against)."""
    try:
        cls = ENGINES[name]
    except KeyError:
        raise ValueError(f"unknown fleet engine {name!r}; "
                         f"known: {sorted(ENGINES)}") from None
    return cls(program, local_iters, seed, augment, quantize, mesh=mesh)


# -----------------------------------------------------------------------------
# row adapters: the aggregation paths accept either engine's output
# -----------------------------------------------------------------------------
def take_rows(rows, positions: Sequence[int]):
    """Select client rows (by position in the engine's output order) keeping
    the representation: list -> sub-list, StackedRows -> gathered
    StackedRows."""
    if isinstance(rows, StackedRows):
        sel = jnp.asarray(np.asarray(positions, np.int32))
        return StackedRows(jax.tree_util.tree_map(lambda a: a[sel],
                                                  rows.tree))
    return [rows[i] for i in positions]


def rows_as_list(rows, positions: Sequence[int]) -> List[Params]:
    """Per-client pytrees for paths that need them (e.g. the reference
    per-client top-k delta compression with error feedback)."""
    if isinstance(rows, StackedRows):
        return [jax.tree_util.tree_map(lambda a: a[i], rows.tree)
                for i in positions]
    return [rows[i] for i in positions]
