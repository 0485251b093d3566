"""Federated round loops: classic FL, SplitFed (static OP), and FedAdapt.

Generic over every registered config: the model side is a
``models.split_program.SplitProgram`` (VGG, dense/moe/vlm, ssm, hybrid,
encdec all train through the same offloading-point execution path), the
planning side a ``fl.planner.Planner`` (static OP, the paper's RL
controller, or the bandwidth-greedy heuristic).

The model updates are *real* JAX training through the actual split execution
path so the offloading cut is exercised; the round *times* come from the
Eq. 1 cost model (paper-calibrated device speeds) — matching how this
CPU-only container can be faithful to a physical testbed.  When a
``fl.comm.Transport`` is supplied, communication time is accounted through
it instead of Eq. 1's built-in network term: cut activations (optionally
int8-quantized via kernels/quant_transfer, which also shrinks the modelled
bytes) and the per-round weight delta sync (optionally top-k sparsified via
kernels/topk_compress) both flow through ``Transport.transfer_time``.

How the K clients' local SGD actually executes is delegated to a *fleet
engine* (``fl/fleet.py``, selected by ``FLConfig.engine``): the
``"sequential"`` engine loops clients in Python (one dispatch per client
iteration), the ``"batched"`` engine vmaps OP groups over a scanned round
(one dispatch per group) for fleet-scale simulation — same seeds, same
history up to float32 summation order (benchmarks/fleet_scaling.py measures
the throughput gap).

The server step — aggregate survivor deltas, top-k error-feedback
sparsification, optional int8 delta quantization, apply to the global —
runs by default as ONE compiled flat-buffer program per round
(``fl/flatbuf.py``, selected by ``FLConfig.server_step``): O(1) device
dispatches instead of the reference per-leaf tree_map path's O(K x leaves).
``server_step="reference"`` keeps the per-leaf baseline for equivalence
tests and benchmarks; the two agree to fp32 tolerance (the fused weighted
reduction is a single matvec, so client summation order differs).

Fleet scale is opt-in per config: ``FLConfig.cohort_size`` samples a seeded
per-round cohort from the registered fleet (fl/cohort.py) — only the
cohort trains, only its error-feedback rows are device-resident (the rest
virtualized in a host-side ``EFStore`` with prefetch overlapped with local
training) — and ``FLConfig.num_edges`` splits aggregation into a two-tier
edge/root server (fl/hierarchy.py) where the root only ever sees one
pre-reduced row per edge.  ``cohort_size=K`` with one edge reproduces the
flat full-participation loop bitwise; ``benchmarks/hierarchy.py`` drives a
simulated million-client fleet through these paths.

Fault tolerance is first-class: deadline straggler drops, failure injection,
atomic checkpoints with bitwise resume (params plus the run's aux state:
top-k error feedback, controller normalizer, failure-RNG position), and
elastic membership (all drilled in tests/test_runtime.py).

This loop is *synchronous*: every round barriers on the slowest client.
``fl/async_loop.run_federated_async`` is the event-driven alternative —
buffered, staleness-discounted aggregation on a virtual clock — sharing
this module's ``RoundClock`` time accounting and reproducing this loop
exactly at ``buffer_size=K, staleness_discount=0``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint import CheckpointManager
from repro.core.controller import FedAdaptController
from repro.core.env import SimulatedCluster
from repro.data.loader import FleetLoader
from repro.fl.cohort import CohortSampler, EFStore
from repro.fl.fedavg import fedavg_delta_stacked, model_bytes
from repro.fl.comm import Transport
from repro.fl.flatbuf import (
    get_root_step,
    get_server_step,
    reference_server_step,
)
from repro.fl.fleet import StackedRows, get_engine, rows_as_list, take_rows
from repro.fl.hierarchy import hierarchical_apply
from repro.fl.state import base_state_tree, ef_template_len
from repro.fl.planner import FedAdaptPlanner, Planner, StaticPlanner
from repro.models.split_program import get_split_program
from repro.runtime.failures import FailureInjector
from repro.runtime.straggler import deadline_mask, deadline_value, reweight


@dataclasses.dataclass
class FLConfig:
    rounds: int = 100
    local_iters: int = 10
    batch_size: int = 100
    lr: float = 0.01
    lr_drop_round: int = 50          # paper: 0.001 from round 50
    lr_drop_factor: float = 0.1
    mode: str = "fl"                 # fl | sfl | fedadapt
    static_op: Optional[int] = None  # sfl: uniform OP for all devices
    deadline_factor: float = 0.0     # >0 enables straggler drop
    fail_prob: float = 0.0
    augment: bool = True             # horizontal flip p=0.5 (paper §V-B)
    quantize_transfer: bool = False  # int8 smashed data across the cut
    delta_density: float = 1.0       # <1: top-k sparsified weight deltas
    quantize_deltas: bool = False    # int8 wire format for the delta sync
                                     # (4x fewer upload bytes; quant error is
                                     # folded into the error feedback when
                                     # delta_density < 1)
    engine: str = "sequential"       # local-training engine: sequential |
                                     # batched (vmap'd OP groups, fl/fleet.py)
    server_step: str = "fused"       # aggregation path: fused (one compiled
                                     # flat-buffer program, fl/flatbuf.py) |
                                     # reference (per-leaf tree_map baseline)
    client_widths: Optional[Sequence[float]] = None
                                     # per-client HeteroFL width fractions in
                                     # (0, 1] (fl/hetero.py): weak clients
                                     # train a width-slice subnetwork and the
                                     # server aggregates across widths with
                                     # per-coordinate coverage counts; None
                                     # keeps every client full-width (the
                                     # homogeneous paths stay bitwise)
    cohort_size: int = 0             # >0: every round trains a seeded
                                     # cohort of this many clients sampled
                                     # from the registered fleet
                                     # (fl/cohort.py); EF state for the
                                     # rest is virtualized host-side in an
                                     # EFStore.  0 keeps legacy
                                     # full-fleet participation;
                                     # cohort_size=K matches it bitwise
    num_edges: int = 0               # >0: two-tier edge/root aggregation
                                     # (fl/hierarchy.py; fused server_step
                                     # only) — edges pre-reduce, the root
                                     # never sees per-client rows.
                                     # num_edges=1 is bitwise the flat
                                     # server; 0 keeps the single tier
    # --- async runtime knobs (fl/async_loop.run_federated_async) ----------
    buffer_size: int = 0             # aggregate once this many client
                                     # updates arrive; 0 -> K (and with
                                     # staleness_discount=0 that special
                                     # case reproduces this sync loop)
    staleness_discount: float = 0.0  # a in the polynomial staleness
                                     # discount (1 + s)^-a on update weights
    max_staleness: Optional[int] = None  # drop updates staler than this
                                         # (None: apply every update)
    mesh_shape: Optional[Sequence[int]] = None
                                     # (data, model) device-mesh shape for
                                     # the sharded flat-buffer server step
                                     # (fl/flatbuf.ShardedFlatLayout over
                                     # parallel.sharding.make_flat_mesh):
                                     # the flat param vector shards along
                                     # 'model' in whole blocks, stacked
                                     # client rows along 'data', and params
                                     # are placed via param_pspecs so split
                                     # rounds run mesh-sharded end to end.
                                     # With engine="batched" local training
                                     # itself goes mesh-parallel: each OP-
                                     # group chunk's client axis splits
                                     # along 'data' under a shard_map fleet
                                     # step (fl/fleet.py); "sequential"
                                     # keeps single-device local training
                                     # and shards only the server step.
                                     # Requires server_step="fused" and
                                     # data*model visible devices.  None =
                                     # the exact legacy single-device path,
                                     # bitwise (asserted in
                                     # tests/test_sharded_flatbuf.py and
                                     # tests/test_mesh_fleet.py)
    seed: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0


def _resolve_planner(
    fl: FLConfig,
    native_op: int,
    planner: Optional[Planner],
    controller: Optional[FedAdaptController],
    sim: Optional[SimulatedCluster],
) -> Planner:
    if planner is not None:
        return planner
    if fl.mode == "fedadapt" and controller is not None and sim is not None:
        return FedAdaptPlanner(controller, explore=False)
    if fl.mode == "sfl":
        return StaticPlanner(fl.static_op if fl.static_op is not None
                             else native_op)
    return StaticPlanner(native_op)


def _resolve_mesh(fl: FLConfig, fused: bool):
    """``FLConfig.mesh_shape`` -> the ``(data, model)`` Mesh (or ``None``
    for the exact legacy single-device path).  Shared by the sync and
    async loops so both thread the same mesh through layout, server step,
    params placement and checkpointing."""
    if fl.mesh_shape is None:
        return None
    if not fused:
        raise ValueError(
            "mesh_shape runs through the fused flat-buffer server step; "
            "server_step='reference' is the single-device per-leaf oracle")
    from repro.parallel.sharding import make_flat_mesh
    return make_flat_mesh(fl.mesh_shape)


def _zero_errors(K: int, layout) -> jnp.ndarray:
    """Eagerly zero-initialized per-client error-feedback state, one flat
    row per client in the server-step layout: identical numerics to a lazy
    ``None`` start (top-k adds zeros), but a *fixed* array shape so the
    state can live in checkpoints and be gathered/scattered by the fused
    server step in one dispatch."""
    return jnp.zeros((K, layout.padded), jnp.float32)


def _delta_trees(params, client_params: List) -> List:
    """Per-client fp32 weight deltas vs the current global (the reference
    server step's per-leaf input; the fused path never materializes these)."""
    return [jax.tree_util.tree_map(
        lambda c, g: c.astype(jnp.float32) - g.astype(jnp.float32),
        cp, params) for cp in client_params]


class RoundClock:
    """Per-device round-time accounting shared by the synchronous loop and
    the async runtime (fl/async_loop.py).

    Compute comes from the Eq. 1 cost model (``SimulatedCluster``); when a
    ``Transport`` is supplied, communication is charged through it instead
    of Eq. 1's built-in network term: per-iteration cut round-trips
    (activations up — optionally int8-quantized — gradients back) plus one
    weight-delta sync (``model_bytes * delta_density`` up, full model
    down).  Zero-bandwidth links yield ``inf`` times (``Transport``
    returns ``inf``), which the deadline path drops and the async runtime
    models as a never-reporting client."""

    def __init__(self, program, fl: FLConfig, K: int, seq: Optional[int],
                 params, sim: Optional[SimulatedCluster] = None,
                 transport: Optional[Transport] = None,
                 compute_scale: Optional[np.ndarray] = None,
                 edge_transport: Optional[Transport] = None):
        self.program = program
        self.fl = fl
        self.K = K
        self.seq = seq
        self.sim = sim
        self.transport = transport
        self.edge_transport = edge_transport
        self.native_op = program.native_op
        self.model_bytes = float(model_bytes(params))  # sizes are static
        # per-client compute multiplier (HeteroFL width**2, fl/hetero.py);
        # None leaves every path's arithmetic untouched
        self.compute_scale = (np.asarray(compute_scale, np.float64)
                              if compute_scale is not None else None)

    def comm_times(self, ops: List[int], round_idx: int) -> np.ndarray:
        """Per-device comm time through the Transport: per-iteration cut
        round-trips (acts out, grads back) + one weight-delta sync.  The
        iteration count follows the sim's notion of a round when present so
        compute and comm stay on the same clock."""
        assert self.transport is not None
        fl, sim = self.fl, self.sim
        iters = sim.iterations if sim is not None else fl.local_iters
        out = []
        for k, op in enumerate(ops):
            t = 0.0
            if op < self.native_op:
                up = self.program.cut_bytes(op, fl.batch_size, self.seq,
                                            quantize=fl.quantize_transfer)
                down = self.program.cut_bytes(op, fl.batch_size, self.seq)
                t += iters * self.transport.round_comm_time(
                    up, down, round_idx, k)
            up = self.model_bytes * fl.delta_density
            if fl.quantize_deltas:
                # int8 wire format: 1 byte/entry vs fp32's 4 (the per-block
                # fp32 scales are ~0.1% overhead and are not modelled)
                up *= 0.25
            t += self.transport.round_comm_time(up, self.model_bytes,
                                                round_idx, k)
            out.append(t)
        return np.asarray(out)

    def edge_hop_times(self, num_edges: int, round_idx: int) -> np.ndarray:
        """Per-edge edge->root comm time for one aggregation under the
        two-tier server: the edge's pre-reduced fp32 row up (model-sized —
        edge rows are dense; top-k/int8 compression lives on the
        client->edge hop) plus the model broadcast back down, through
        ``edge_transport`` with the edge index as the link id.  Empty/zero
        without an ``edge_transport`` — the free-root-hop default that
        keeps single-tier configurations bitwise unchanged."""
        if self.edge_transport is None or num_edges <= 0:
            return np.zeros(max(int(num_edges), 0))
        return np.asarray([
            self.edge_transport.round_comm_time(
                self.model_bytes, self.model_bytes, round_idx, e)
            for e in range(int(num_edges))])

    def times(self, ops: List[int], round_idx: int):
        """(total per-device round times, comm component)."""
        scale = self.compute_scale
        if self.transport is not None:
            comm = self.comm_times(ops, round_idx)
            comp = (self.sim.round_compute_times(ops, round_idx)
                    if self.sim is not None else np.zeros(self.K))
            if scale is not None:
                comp = comp * scale
            return comp + comm, comm
        if self.sim is not None:
            if scale is not None:
                # Eq. 1's built-in network term is width-independent: scale
                # only the compute component
                comp = self.sim.round_compute_times(ops, round_idx)
                total = self.sim.round_times(ops, round_idx)
                return comp * scale + (total - comp), np.zeros(self.K)
            return self.sim.round_times(ops, round_idx), np.zeros(self.K)
        if scale is not None:
            return np.ones(self.K) * scale, np.zeros(self.K)
        return np.ones(self.K), np.zeros(self.K)


def run_federated(
    cfg,
    clients_data: List[Dict[str, np.ndarray]],
    test_data: Dict[str, np.ndarray],
    fl: FLConfig,
    sim: Optional[SimulatedCluster] = None,
    controller: Optional[FedAdaptController] = None,
    resume: bool = False,
    planner: Optional[Planner] = None,
    transport: Optional[Transport] = None,
    edge_transport: Optional[Transport] = None,
) -> Dict[str, np.ndarray]:
    """Train any registered config federated with per-round offloading.

    ``cfg`` is a ``VGGConfig`` or any ``ModelConfig`` family with a
    registered ``SplitProgram``.  Returns history: per-round eval metric
    (``accuracy``: classification accuracy for VGG, -CE loss for LMs),
    round/comm times, per-device OPs, drop counts, and — under the
    two-tier server — the per-round edge->root hop time (``edge_time``,
    charged through ``edge_transport`` and added to ``round_time``).
    """
    program = get_split_program(cfg)
    K = len(clients_data)
    params = program.init(jax.random.PRNGKey(fl.seed))
    if fl.server_step not in ("fused", "reference"):
        raise ValueError(f"unknown server_step {fl.server_step!r}; "
                         f"known: fused, reference")
    fused = fl.server_step == "fused"
    mesh = _resolve_mesh(fl, fused)
    if mesh is not None:
        params = program.shard_params(params, mesh)
    # keep the legacy call signature when no mesh is configured --
    # mesh_shape=None must not even pass the kwarg (custom
    # SplitPrograms may predate it)
    layout = (program.flat_layout(params, mesh=mesh)
              if mesh is not None else program.flat_layout(params))
    loaders = FleetLoader.for_clients(clients_data, fl.batch_size,
                                      seed=fl.seed)
    # a cohort fleet's data stays on the host: only every-round training
    # makes the whole fleet each round's working set (fl/fleet.py)
    engine = get_engine(fl.engine, program, fl.local_iters, fl.seed,
                        fl.augment, fl.quantize_transfer, mesh=mesh,
                        resident=fl.cohort_size == 0)
    injector = FailureInjector(fl.fail_prob, seed=fl.seed)
    native_op = program.native_op
    seq = (clients_data[0]["tokens"].shape[1]
           if "tokens" in clients_data[0] else None)
    sizes = np.asarray([len(d["labels"]) for d in clients_data], np.float64)
    if not 0 <= fl.cohort_size <= K:
        raise ValueError(f"cohort_size={fl.cohort_size} outside [0, K={K}]")
    if fl.num_edges < 0:
        raise ValueError(f"num_edges={fl.num_edges} must be >= 0")
    if fl.num_edges > 0 and not fused:
        raise ValueError(
            "hierarchical aggregation (num_edges > 0) runs through the "
            "fused flat-buffer server step; server_step='reference' is the "
            "per-client oracle it is tested against, not a tiered path")
    cohort = (CohortSampler(K, fl.cohort_size, seed=fl.seed)
              if fl.cohort_size > 0 else None)
    track_errors = fl.delta_density < 1.0
    # EF representation: dense (K, padded) device array for the legacy
    # full-fleet loop; host-side virtualized EFStore once a cohort caps the
    # device-resident working set at O(cohort_size x padded)
    if not track_errors:
        delta_errors = None
    elif cohort is not None:
        delta_errors = EFStore(K, layout.padded)
    else:
        delta_errors = _zero_errors(K, layout)
    virtualized = isinstance(delta_errors, EFStore)
    from repro.fl.hetero import resolve_hetero
    hetero = resolve_hetero(fl, program, params, layout)
    if hetero is not None and len(hetero) != K:
        raise ValueError(f"client_widths has {len(hetero)} entries for "
                         f"K={K} clients")
    ctl = controller if controller is not None \
        else getattr(planner, "controller", None)

    mgr = None
    start_round = 0
    if fl.checkpoint_dir:
        mgr = CheckpointManager(fl.checkpoint_dir)
        if resume:
            # peek the stored shapes first: the virtualized EF snapshot is
            # sparse (ef/ids + ef/rows with a data-dependent touched count),
            # so the strict restore template is sized off the file
            shapes = mgr.latest_shapes()
            if shapes is not None:
                restored, ck_step = mgr.restore_latest(
                    base_state_tree(params, delta_errors, ctl, K,
                                    template=True,
                                    ef_len=ef_template_len(shapes)))
                params = restored["params"]
                if mesh is not None:
                    # checkpoints hold host numpy; re-place on the mesh so
                    # the resumed run executes the same sharded programs
                    # (bitwise resume — tests/test_sharded_flatbuf.py)
                    params = program.shard_params(params, mesh)
                if track_errors:
                    if virtualized:
                        delta_errors.restore(
                            np.asarray(restored["ef"]["ids"], np.int64),
                            restored["ef"]["rows"])
                    else:
                        delta_errors = jnp.asarray(
                            restored["delta_errors"], jnp.float32)
                if ctl is not None:
                    ctl.baselines = np.asarray(
                        restored["controller"]["baselines"], np.float64)
                    ctl.prev_actions = np.asarray(
                        restored["controller"]["prev_actions"], np.float32)
                start_round = int(ck_step)
                # fast-forward the deterministic loaders so a resumed run
                # sees the exact batches of an uninterrupted one (bitwise
                # resume — tests/test_runtime.py, tests/test_async.py).
                # Only rounds a client was ALIVE *and in the cohort* drew
                # from its stream, and both the failure masks and the
                # cohort draws are keyed by round index (pure functions of
                # the seed), so the exact per-client consumption replays
                # without any stored state — untouched clients stay
                # unmaterialized in the lazy FleetLoader
                alive_rounds = np.zeros(K, np.int64)
                for rr in range(start_round):
                    m = injector.round_mask(K, round_idx=rr)
                    if cohort is not None:
                        m = m & cohort.member_mask(rr)
                    alive_rounds += m
                for k in np.flatnonzero(alive_rounds):
                    loaders.skip_client(int(k),
                                        int(alive_rounds[k]) * fl.local_iters)

    # --- round time accounting -------------------------------------------
    clock = RoundClock(program, fl, K, seq, params, sim=sim,
                       transport=transport,
                       compute_scale=(hetero.compute_scale
                                      if hetero is not None else None),
                       edge_transport=edge_transport)

    # --- server step: one compiled flat-buffer program per round ----------
    # (fl/flatbuf.py; cached per layout/density/quantize, reused across
    # rounds and shared with the async runtime)
    step = get_server_step(layout, fl.delta_density, fl.quantize_deltas) \
        if fused else None
    root = get_root_step(layout) if fused and fl.num_edges > 0 else None
    g_flat = layout.flatten(params) if fused else None

    # round-0 baselines (classic FL, no offloading)
    times, _ = clock.times([native_op] * K, 0)
    if controller is not None and controller.baselines is None:
        controller.begin(times)
    plan = _resolve_planner(fl, native_op, planner, controller, sim)
    plan.begin(times)

    hist: Dict[str, list] = {"accuracy": [], "round_time": [], "ops": [],
                             "times": [], "comm_time": [], "dropped": [],
                             "edge_time": []}
    eval_fn = jax.jit(lambda p, b: program.eval_metric(p, b))
    test_batch = {k: jnp.asarray(v) for k, v in test_data.items()}

    # Each round runs as sibling profiler spans (TraceAnnotation: about a
    # microsecond when no trace is active; docs/ARCHITECTURE.md, "Observing
    # a round"): fl.plan, fl.train, fl.account, fl.aggregate, fl.sync and,
    # when one is due, fl.checkpoint.  The engines add fl.stack and fl.put
    # inside fl.train.
    for r in range(start_round, fl.rounds):
        lr = fl.lr * (fl.lr_drop_factor if r >= fl.lr_drop_round else 1.0)
        # --- plan offloading for this round --------------------------------
        with TraceAnnotation("fl.plan", round=r):
            bandwidths = sim.bandwidths(r) if sim is not None else None
            ops = plan.plan(r, times, bandwidths)
            alive = injector.round_mask(K, round_idx=r)
            if cohort is not None:
                # only this round's seeded cohort participates; everyone
                # else counts as dropped for this round's accounting
                alive &= cohort.member_mask(r)
                if virtualized:
                    # stage the live cohort's EF rows on the store's worker
                    # thread — the host-side gather overlaps the cohort's
                    # local training, and the post-training fetch
                    # (survivors are a subset of the live cohort) consumes
                    # the staged rows
                    delta_errors.prefetch(np.flatnonzero(alive))
        # --- local training (fleet engine) ----------------------------------
        with TraceAnnotation("fl.train", round=r):
            idxs, rows = engine.run_round(
                params, loaders, ops,
                [int(k) for k in np.flatnonzero(alive)], r, lr,
                hetero=hetero)
        # --- timing + straggler handling ------------------------------------
        with TraceAnnotation("fl.account", round=r):
            times, comm = clock.times(ops, r)
            keep = np.ones(K, bool)
            if fl.deadline_factor > 0:
                keep = deadline_mask(times, fl.deadline_factor)
            keep &= alive
            weights = reweight(sizes, keep)
            kept_pos = [i for i, k in enumerate(idxs) if keep[k]]
            surv_idx = [idxs[i] for i in kept_pos]
            surv_w = [weights[k] for k in surv_idx]
        # --- server step ----------------------------------------------------
        with TraceAnnotation("fl.aggregate", round=r, clients=len(surv_idx)):
            edges_used = 0
            if kept_pos:
                mask_rows = (hetero.rows(surv_idx) if hetero is not None
                             else None)
                if fused:
                    # fused flat-buffer server step: stack survivor
                    # deltas, top-k error feedback, optional int8, weighted
                    # apply — all one compiled dispatch (plus one stack, one
                    # unflatten); with num_edges > 0 the same pipeline runs
                    # tiered (fl/hierarchy.py: per-edge reduce, root apply)
                    deltas = layout.rows_to_deltas(
                        take_rows(rows, kept_pos), g_flat)
                    ids = jnp.asarray(np.asarray(surv_idx, np.int32))
                    if not track_errors:
                        err_rows = None
                    elif virtualized:
                        err_rows = delta_errors.fetch(surv_idx)
                    else:
                        err_rows = delta_errors[ids]
                    if fl.num_edges > 0:
                        g_flat, new_err, edges_used = hierarchical_apply(
                            step, root, g_flat, deltas, surv_w, err_rows,
                            mask_rows, num_edges=fl.num_edges)
                    else:
                        g_flat, new_err = step(g_flat, deltas, surv_w,
                                               err_rows, masks=mask_rows)
                    if track_errors:
                        if virtualized:
                            delta_errors.store(surv_idx, new_err)
                        else:
                            delta_errors = delta_errors.at[ids].set(new_err)
                    params = layout.unflatten(g_flat)
                    if not layout.exact_fp32:
                        # narrower param dtypes round on unflatten:
                        # re-derive the flat master from the rounded params
                        # so checkpoints (which store params) stay a
                        # complete description of the run state; for fp32
                        # this would be a bitwise no-op
                        g_flat = layout.flatten(params)
                elif hetero is None and not track_errors and \
                        not fl.quantize_deltas and \
                        isinstance(rows, StackedRows):
                    # reference path, plain averaging, batched engine:
                    # keep the pre-fused stacked tensordot (one op per leaf)
                    # rather than degrading to a K-wide per-client loop
                    survivors = take_rows(rows, kept_pos)
                    params = fedavg_delta_stacked(params, survivors.tree,
                                                  surv_w)
                else:
                    # reference per-leaf path (O(K x leaves) dispatches):
                    # the equivalence baseline for tests and benchmarks
                    ids = jnp.asarray(np.asarray(surv_idx, np.int32))
                    if not track_errors:
                        err_rows = None
                    elif virtualized:
                        err_rows = delta_errors.fetch(surv_idx)
                    else:
                        err_rows = delta_errors[ids]
                    params, new_err = reference_server_step(
                        layout, params, _delta_trees(
                            params, rows_as_list(rows, kept_pos)),
                        surv_w, err_rows, density=fl.delta_density,
                        quantize=fl.quantize_deltas, masks=mask_rows)
                    if track_errors:
                        if virtualized:
                            delta_errors.store(surv_idx, new_err)
                        else:
                            delta_errors = delta_errors.at[ids].set(new_err)
        plan.feedback(times)
        # --- evaluation + checkpoint ----------------------------------------
        # the host waits here for the round's device work
        with TraceAnnotation("fl.sync", round=r):
            acc = float(eval_fn(params, test_batch))
        hist["accuracy"].append(acc)
        if keep.any():
            wall = float(np.max(times[keep]))
        elif fl.deadline_factor > 0:
            # every client missed the deadline (e.g. dead links pushed all
            # times to inf): the server waited the deadline out, not inf
            wall = deadline_value(times, fl.deadline_factor)
        else:
            finite = times[np.isfinite(times)]
            wall = float(finite.max()) if finite.size else 0.0
        # edge->root hop of the two-tier server: the slowest active edge
        # extends the round (0.0 without an edge_transport, which keeps
        # flat configurations bitwise unchanged)
        edge_wall = 0.0
        if edges_used and edge_transport is not None:
            edge_wall = float(np.max(clock.edge_hop_times(edges_used, r)))
            wall += edge_wall
        hist["round_time"].append(wall)
        hist["edge_time"].append(edge_wall)
        hist["ops"].append(list(ops))
        hist["times"].append(times.copy())
        hist["comm_time"].append(comm.copy())
        hist["dropped"].append(int(K - keep.sum()))
        if mgr is not None and fl.checkpoint_every and \
                (r + 1) % fl.checkpoint_every == 0:
            with TraceAnnotation("fl.checkpoint", round=r):
                mgr.save(base_state_tree(params, delta_errors, ctl, K), r + 1)

    hist_np = {k: np.asarray(v) for k, v in hist.items()}
    hist_np["params"] = params
    return hist_np
