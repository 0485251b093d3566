"""Event-driven asynchronous federated runtime (virtual-clock).

FedAdapt's synchronous loop barriers every round on its slowest device —
offloading *shrinks* the straggler (the paper's claim) but cannot remove
the barrier.  This module adds the complementary mitigation surveyed by
Pfeiffer et al. (arXiv:2307.09182): buffered asynchronous aggregation with
staleness-discounted weights (FedBuff, Nguyen et al.; FedAsync, Xie et
al.).  Each device finishes its local split-training at its own modeled
time — Eq. 1 compute via ``SimulatedCluster`` plus comm via ``Transport``,
the same ``fl.loop.RoundClock`` accounting as the synchronous loop — and
reports to a server that aggregates as soon as ``FLConfig.buffer_size``
updates arrive, then immediately re-dispatches each reporting client with
a freshly planned Offloading Point against the new params.

Aggregation: each buffered client delta (taken against the params version
the client was dispatched with) is weighted by ``n_k * (1+s_k)^-a`` where
``s_k`` is the staleness in server versions and ``a`` is
``FLConfig.staleness_discount``; updates staler than
``FLConfig.max_staleness`` are discarded.  With ``buffer_size=K`` and
``staleness_discount=0`` every dispatch is a synchronous round and the
runtime reproduces ``run_federated``'s history exactly (the equivalence
drill in tests/test_async.py).  The buffered aggregation itself runs
through the same fused flat-buffer server step as the synchronous loop
(``fl/flatbuf.py``, one compiled dispatch per aggregation; reports carry
flat delta rows) — ``FLConfig.server_step="reference"`` selects the
per-leaf baseline.  ``FLConfig.client_widths`` (fl/hetero.py) assigns
HeteroFL width-scaled subnetworks: weak clients train a width slice, the
server aggregates across widths with per-coordinate coverage counts, and a
width-``w`` client's modeled compute shrinks by ``w**2``.

The model updates are *real* JAX training through the same fleet engines
as the synchronous loop (``FLConfig.engine``): all clients re-dispatched
at one virtual instant train in one ``engine.run_round`` call, so clients
sharing an (OP, width) fuse into a single vmap'd dispatch under the
batched engine.  Virtual time is tracked by ``runtime.scheduler.
EventQueue``; clients on dead links (``Transport`` returns ``inf``) simply
never report, and a fully-stalled fleet ends the run early instead of
spinning.

Fleet scale mirrors the synchronous loop: ``FLConfig.cohort_size`` keeps
exactly C clients in flight — reporters are replaced at every aggregation
boundary by a seeded draw from the idle fleet (``fl.cohort.CohortSampler
.pick``, keyed by server version), EF state is virtualized in a host-side
``EFStore``, and ``FLConfig.num_edges`` routes each buffered aggregation
through the two-tier edge/root server (fl/hierarchy.py) with the
edge->root hop charged to an ``edge_time`` history column via
``edge_transport``.  ``cohort_size=K`` degenerates to the legacy
all-clients dispatch bitwise.

Checkpoint/resume: ``FLConfig.checkpoint_dir`` + ``checkpoint_every``
snapshot the run at aggregation boundaries.  The key invariant is that at
a boundary (buffer flushed, reporters replaced) exactly C clients (K
without a cohort) have ONE in-flight report event each, so the whole
scheduler state is a fixed-shape table: C timestamps (``inf`` for dead
links) plus C report payloads as flat delta rows (assembled by
``fl.state.async_state_tree`` — shared with the sync loop's tree).  A
resumed run replays the remaining aggregations bitwise (``resume=True``;
the drill in tests/test_chaos.py) — this is what makes mid-drill chaos
replay exact.  Requires an fp32 layout (``FlatLayout.exact_fp32``) so
delta rows round-trip bitwise.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.controller import FedAdaptController
from repro.core.env import SimulatedCluster
from repro.data.loader import FleetLoader
from repro.fl.cohort import CohortSampler, EFStore
from repro.fl.comm import Transport
from repro.fl.flatbuf import (
    get_root_step,
    get_server_step,
    reference_server_step,
)
from repro.fl.fleet import get_engine, rows_as_list
from repro.fl.hetero import resolve_hetero
from repro.fl.hierarchy import hierarchical_apply
from repro.fl.loop import (
    FLConfig,
    RoundClock,
    _delta_trees,
    _resolve_mesh,
    _resolve_planner,
    _zero_errors,
)
from repro.fl.planner import Planner
from repro.fl.state import async_state_tree, ef_template_len
from repro.models.split_program import get_split_program
from repro.runtime.scheduler import EventQueue
from repro.runtime.straggler import reweight


def staleness_weights(sizes, staleness, discount: float) -> np.ndarray:
    """Unnormalized async aggregation weights: ``n_k * (1 + s_k)^-a``
    (polynomial staleness discount — FedAsync's ``s_a(t-tau)``).  ``a=0``
    recovers plain data-size FedAvg weighting."""
    n = np.asarray(sizes, np.float64)
    s = np.asarray(staleness, np.float64)
    return n * (1.0 + s) ** (-float(discount))


@dataclasses.dataclass
class _Report:
    """One client's finished local training, in flight to the server."""
    client: int
    version: int      # params version the client was dispatched with
    op: int
    delta: Any        # f32 param delta vs the dispatch-time params: a flat
                      # layout row (fused server step) or a pytree (reference)
    time: float       # modeled duration (compute + comm) of this dispatch
    comm: float


def run_federated_async(
    cfg,
    clients_data: List[Dict[str, np.ndarray]],
    test_data: Dict[str, np.ndarray],
    fl: FLConfig,
    sim: Optional[SimulatedCluster] = None,
    controller: Optional[FedAdaptController] = None,
    planner: Optional[Planner] = None,
    transport: Optional[Transport] = None,
    edge_transport: Optional[Transport] = None,
    on_aggregate: Optional[Callable[..., None]] = None,
    resume: bool = False,
) -> Dict[str, np.ndarray]:
    """Train any registered config through the async virtual-clock runtime.

    Same contract as ``fl.loop.run_federated`` (one history row per server
    aggregation instead of per synchronous round) plus async columns:
    ``virtual_time`` (the clock at each aggregation), ``staleness`` (mean
    staleness of the applied updates), ``dropped`` counting
    ``max_staleness`` discards, and ``agg_weight_sum`` (the applied
    normalized weight mass — 1.0 whenever any update applied, 0.0 when the
    whole buffer was discarded; the conservation invariant chaos drills
    assert).  ``fl.rounds`` bounds the number of aggregations; the run ends
    early if every in-flight client sits behind a dead link.

    ``on_aggregate(version, params, g_flat=...)`` fires after every server
    aggregation with the new params version; ``g_flat`` is the loop's flat
    global buffer under the fused server step (``None`` otherwise).  This
    is the train->serve publication hook: pass
    ``serving.ParamStore.on_aggregate`` and a live ``ServeEngine`` hot-swaps
    each aggregated model without recompiling (see serving/hotswap.py).

    With ``fl.checkpoint_dir`` set, the run snapshots every
    ``fl.checkpoint_every`` aggregations; ``resume=True`` restores the
    latest snapshot and returns the *suffix* history (rows for the
    remaining aggregations), bitwise identical to the uninterrupted run's
    suffix.
    """
    program = get_split_program(cfg)
    K = len(clients_data)
    if not 0 <= fl.cohort_size <= K:
        raise ValueError(f"cohort_size={fl.cohort_size} outside [0, K={K}]")
    if fl.num_edges < 0:
        raise ValueError(f"num_edges={fl.num_edges} must be >= 0")
    # C = the in-flight set: with a cohort, exactly C clients are training
    # at any instant — reporters are replaced by a seeded draw from the
    # idle fleet at each boundary, so the run walks the whole fleet while
    # the server's working set stays O(C)
    C = fl.cohort_size if fl.cohort_size > 0 else K
    buffer_size = fl.buffer_size if fl.buffer_size > 0 else C
    if not 1 <= buffer_size <= C:
        raise ValueError(f"buffer_size={buffer_size} outside [1, C={C}] "
                         f"(the in-flight cohort)")
    if fl.deadline_factor > 0 or fl.fail_prob > 0:
        raise ValueError(
            "the async runtime replaces deadline drops and failure masks "
            "(a slow client is simply aggregated late); run the sync loop "
            "for deadline_factor/fail_prob scenarios")

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
    if fl.checkpoint_dir and not layout.exact_fp32:
        raise ValueError(
            "async checkpoint/resume needs an fp32 parameter layout "
            "(in-flight deltas are checkpointed as flat rows, which is "
            "only bitwise for fp32)")
    loaders = FleetLoader.for_clients(clients_data, fl.batch_size,
                                      seed=fl.seed)
    # a cohort fleet's data stays on the host: only every-round training
    # makes the whole fleet each round's working set (fl/fleet.py)
    engine = get_engine(fl.engine, program, fl.local_iters, fl.seed,
                        fl.augment, fl.quantize_transfer, mesh=mesh,
                        resident=fl.cohort_size == 0)
    native_op = program.native_op
    seq = (clients_data[0]["tokens"].shape[1]
           if "tokens" in clients_data[0] else None)
    sizes = np.asarray([len(d["labels"]) for d in clients_data], np.float64)
    if fl.num_edges > 0 and fl.server_step != "fused":
        raise ValueError(
            "hierarchical aggregation (num_edges > 0) runs through the "
            "fused flat-buffer server step; server_step='reference' is the "
            "per-client oracle it is tested against, not a tiered path")
    cohort = (CohortSampler(K, C, seed=fl.seed)
              if fl.cohort_size > 0 else None)
    track_errors = fl.delta_density < 1.0
    if not track_errors:
        delta_errors = None
    elif cohort is not None:
        delta_errors = EFStore(K, layout.padded)
    else:
        delta_errors = _zero_errors(K, layout)
    virtualized = isinstance(delta_errors, EFStore)
    hetero = resolve_hetero(fl, program, params, layout)
    if hetero is not None and len(hetero) != K:
        raise ValueError(f"client_widths has {len(hetero)} entries for "
                         f"K={K} clients")
    ctl = controller if controller is not None \
        else getattr(planner, "controller", None)
    # the SAME cached compiled server step as the synchronous loop
    # (fl/flatbuf.py) — sync and async aggregate through one executable
    srv = get_server_step(layout, fl.delta_density, fl.quantize_deltas) \
        if fused else None
    root = get_root_step(layout) if fused and fl.num_edges > 0 else None
    g_flat = layout.flatten(params) if fused else None
    clock = RoundClock(program, fl, K, seq, params, sim=sim,
                       transport=transport,
                       compute_scale=(hetero.compute_scale
                                      if hetero is not None else None),
                       edge_transport=edge_transport)

    mgr = CheckpointManager(fl.checkpoint_dir) if fl.checkpoint_dir else None
    version = 0            # server params version == aggregations so far
    queue = EventQueue()
    comm = np.zeros(K)
    current_ops = [native_op] * K
    in_flight = np.zeros(K, bool)
    last_agg_clock = 0.0
    restored_state = None
    if mgr is not None and resume:
        # shape peek first: the virtualized EF snapshot is sparse with a
        # data-dependent touched-row count (fl/state.py)
        shapes = mgr.latest_shapes()
        if shapes is not None:
            restored_state, step = mgr.restore_latest(
                async_state_tree(params, delta_errors, ctl, K, C, layout,
                                 template=True,
                                 ef_len=ef_template_len(shapes)))

    if restored_state is not None:
        version = int(step)
        params = restored_state["params"]
        if mesh is not None:
            # checkpoints hold host numpy; re-place on the mesh so the
            # resumed run executes the same sharded programs
            params = program.shard_params(params, mesh)
        if fused:
            g_flat = layout.flatten(params)
        if track_errors:
            if virtualized:
                delta_errors.restore(
                    np.asarray(restored_state["ef"]["ids"], np.int64),
                    restored_state["ef"]["rows"])
            else:
                delta_errors = jnp.asarray(restored_state["delta_errors"],
                                           jnp.float32)
        if ctl is not None:
            ctl.baselines = np.asarray(
                restored_state["controller"]["baselines"], np.float64)
            ctl.prev_actions = np.asarray(
                restored_state["controller"]["prev_actions"], np.float32)
        st = restored_state["async"]
        queue = EventQueue(start_time=float(st["clock"][0]))
        last_agg_clock = float(st["clock"][1])
        times = np.asarray(st["times"], np.float64)
        comm = np.asarray(st["comm"], np.float64)
        current_ops = [int(o) for o in st["ops"]]
        loaders.restore([(int(e), int(c)) for e, c in st["loader_state"]])
        # re-inflate the C in-flight report events in saved (t, seq) order:
        # pushes re-assign fresh FIFO sequence numbers, so same-time ties
        # pop in the same order as the uninterrupted run
        in_flight[np.asarray(st["ev_client"], np.int64)] = True
        for i in range(C):
            row = jnp.asarray(st["ev_delta"][i], jnp.float32)
            rpt = _Report(int(st["ev_client"][i]),
                          int(st["ev_version"][i]),
                          int(st["ev_op"][i]),
                          row if fused else layout.unflatten(row),
                          float(st["ev_dur"][i]),
                          float(st["ev_comm"][i]))
            queue.push(float(st["ev_t"][i]), rpt)
        plan = _resolve_planner(fl, native_op, planner, controller, sim)
        plan.begin(times)   # FedAdaptPlanner skips: baselines are restored
    else:
        # round-0 baselines (classic FL, no offloading) — same normalizer
        # as the synchronous loop, so planners behave identically in both
        # runtimes
        times, _ = clock.times([native_op] * K, 0)
        if controller is not None and controller.baselines is None:
            controller.begin(times)
        plan = _resolve_planner(fl, native_op, planner, controller, sim)
        plan.begin(times)

    hist: Dict[str, list] = {"accuracy": [], "round_time": [], "ops": [],
                             "times": [], "comm_time": [], "dropped": [],
                             "virtual_time": [], "staleness": [],
                             "agg_weight_sum": [], "edge_time": []}
    eval_fn = jax.jit(lambda p, b: program.eval_metric(p, b))
    test_batch = {k: jnp.asarray(v) for k, v in test_data.items()}

    def dispatch(ks: List[int]) -> None:
        """Plan fresh OPs, run the clients' local training (one fleet-engine
        call: same-(OP, width) clients fuse into one vmap'd dispatch), and
        schedule their reports at ``now + modeled duration``."""
        lr = fl.lr * (fl.lr_drop_factor if version >= fl.lr_drop_round
                      else 1.0)
        bandwidths = sim.bandwidths(version) if sim is not None else None
        ops = plan.plan(version, times, bandwidths)
        in_flight[list(ks)] = True
        for k in ks:
            current_ops[k] = int(ops[k])
        idxs, rows = engine.run_round(params, loaders, ops, list(ks),
                                      version, lr, hetero=hetero)
        t_all, c_all = clock.times(ops, version)
        if fused:
            # one dispatch for the whole cohort: flatten rows, subtract the
            # dispatch-version flat global; each report carries its row
            deltas_flat = layout.rows_to_deltas(rows, g_flat)
            per_client = [deltas_flat[pos] for pos in range(len(idxs))]
        else:
            per_client = _delta_trees(
                params, rows_as_list(rows, list(range(len(idxs)))))
        for pos, k in enumerate(idxs):
            rpt = _Report(k, version, int(ops[k]), per_client[pos],
                          float(t_all[k]), float(c_all[k]))
            queue.push(queue.now + rpt.time, rpt)

    def save_checkpoint() -> None:
        """Snapshot at an aggregation boundary: buffer empty, exactly C
        clients in flight (the fixed-shape invariant; fl/state.py asserts
        the count)."""
        events = [(t, rpt, rpt.delta if fused else layout.flatten(rpt.delta))
                  for t, _, rpt in queue.snapshot()]
        mgr.save(async_state_tree(
            params, delta_errors, ctl, K, C, layout,
            clock=[queue.now, last_agg_clock], times=times, comm=comm,
            ops=current_ops, loader_state=loaders.state(), events=events),
            version)

    if restored_state is None:
        dispatch([int(k) for k in cohort.members(0)] if cohort is not None
                 else list(range(K)))
    buffer: List[_Report] = []

    while version < fl.rounds:
        if len(buffer) < buffer_size and np.isfinite(queue.peek_time()):
            _, rpt = queue.pop()
            times[rpt.client] = rpt.time
            comm[rpt.client] = rpt.comm
            in_flight[rpt.client] = False
            buffer.append(rpt)
            continue
        if not buffer:
            break          # every in-flight client is behind a dead link
        # A short buffer here means the remaining in-flight clients can
        # never report (dead links): flush the finished updates rather than
        # discarding real training — the live fleet just shrank below
        # buffer_size.

        # --- server step: staleness-discounted buffered FedAvg -----------
        edges_used = 0
        buffer.sort(key=lambda e: e.client)
        stale = {e.client: version - e.version for e in buffer}
        fresh = [e for e in buffer
                 if fl.max_staleness is None
                 or stale[e.client] <= fl.max_staleness]
        if fresh:
            s = np.asarray([stale[e.client] for e in fresh], np.float64)
            w_full = np.zeros(K, np.float64)
            for e, wk in zip(fresh, staleness_weights(
                    [sizes[e.client] for e in fresh], s,
                    fl.staleness_discount)):
                w_full[e.client] = wk
            weights = reweight(w_full, w_full > 0)
            w_list = [weights[e.client] for e in fresh]
            fresh_ids = [e.client for e in fresh]
            ids = jnp.asarray(np.asarray(fresh_ids, np.int32))
            if not track_errors:
                err_rows = None
            elif virtualized:
                err_rows = delta_errors.fetch(fresh_ids)
            else:
                err_rows = delta_errors[ids]
            mask_rows = (hetero.rows(fresh_ids)
                         if hetero is not None else None)
            if fused:
                stacked = jnp.stack([e.delta for e in fresh])
                if fl.num_edges > 0:
                    # two-tier server (fl/hierarchy.py): per-edge reduce of
                    # the buffered rows, root combine + apply
                    g_flat, new_err, edges_used = hierarchical_apply(
                        srv, root, g_flat, stacked, w_list, err_rows,
                        mask_rows, num_edges=fl.num_edges)
                else:
                    g_flat, new_err = srv(g_flat, stacked, w_list, err_rows,
                                          masks=mask_rows)
                params = layout.unflatten(g_flat)
                if not layout.exact_fp32:
                    # keep the flat master equal to the rounded params
                    # (see fl/loop.py; fp32 needs no resync)
                    g_flat = layout.flatten(params)
            else:
                params, new_err = reference_server_step(
                    layout, params, [e.delta for e in fresh], w_list,
                    err_rows, density=fl.delta_density,
                    quantize=fl.quantize_deltas, masks=mask_rows)
            if track_errors:
                if virtualized:
                    delta_errors.store(fresh_ids, new_err)
                else:
                    delta_errors = delta_errors.at[ids].set(new_err)
            mean_stale = float(s.mean())
            weight_sum = float(np.sum(w_list))
        else:
            mean_stale = 0.0
            weight_sum = 0.0
        # edge->root hop of the two-tier server: reported as its own
        # history column, charged through edge_transport at this
        # aggregation's version (the virtual clock is event-driven and is
        # not advanced by the hop — a free hop without an edge_transport)
        edge_wall = 0.0
        if edges_used and edge_transport is not None:
            edge_wall = float(np.max(
                clock.edge_hop_times(edges_used, version)))
        version += 1
        if on_aggregate is not None:
            on_aggregate(version, params, g_flat=g_flat if fused else None)
        plan.feedback(times)
        # --- history row (one per aggregation) ---------------------------
        hist["accuracy"].append(float(eval_fn(params, test_batch)))
        hist["round_time"].append(queue.now - last_agg_clock)
        hist["ops"].append(list(current_ops))
        hist["times"].append(times.copy())
        hist["comm_time"].append(comm.copy())
        hist["dropped"].append(len(buffer) - len(fresh))
        hist["virtual_time"].append(queue.now)
        hist["staleness"].append(mean_stale)
        hist["agg_weight_sum"].append(weight_sum)
        hist["edge_time"].append(edge_wall)
        last_agg_clock = queue.now
        # --- re-dispatch at the new version ------------------------------
        # without a cohort: the reporting clients themselves (legacy);
        # with one: a seeded draw of |reporters| replacements from the
        # idle fleet, keyed by version — the in-flight set stays exactly C
        # while participation walks the whole registered fleet.  With
        # cohort_size=K the idle set IS the reporter set, so the draw
        # degenerates to the legacy redispatch bitwise.
        reporters = sorted(e.client for e in buffer)
        buffer = []
        if version < fl.rounds:
            if cohort is not None:
                redispatch = [int(k) for k in cohort.pick(
                    version, np.flatnonzero(~in_flight), len(reporters))]
            else:
                redispatch = reporters
            dispatch(redispatch)
            # --- reconnection: unreachable clients re-register -----------
            # a client dispatched behind a dead link holds an inf event;
            # every boundary it re-fetches the CURRENT model, so when its
            # link recovers (chaos scripts, flapping transports) it reports
            # fresh work instead of being lost to the fleet forever
            stuck = sorted({r.client for r in queue.drop_unreachable()})
            if stuck:
                dispatch(stuck)
            if mgr is not None and fl.checkpoint_every and \
                    version % fl.checkpoint_every == 0:
                save_checkpoint()

    hist_np = {k: np.asarray(v) for k, v in hist.items()}
    hist_np["params"] = params
    return hist_np
