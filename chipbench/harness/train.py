"""Training cells: ``run_federated`` driven for a window of whole rounds.

One ``run_federated`` call runs the whole cell.  The harness passes it a
planner (``WindowPlanner``) that hands every client its fixed OP and marks
the round boundaries: a round runs from one ``plan()`` call to the next, and
the round before each ``plan()`` has ended on the loop's own ``float`` of
the eval metric, which waits for the device.  Rounds 0-2 warm up every
program (set-up) and are the rounds the reference checks; ``plan(3)`` opens
the window and the first ``plan()`` at or past ``--seconds`` closes it and
ends the loop by raising ``StopWindow``.  The planner also copies the global
weights at ``plan(0..3)`` from the loop's frame: the loop hands them to
nothing else, and the copies are taken before the window opens.  A traced
run records the window's whole rounds up to ``TRACE_S`` seconds in: the
per-layer metrics read that traced part, and the profiler's cost stays
within the run's time limit whatever the window's length.

``correct`` compares the program's weights after rounds 1-3 with the plain
reference (``fedref``) started from the same seed: the largest gap of the
eval loss, and of the per-leaf norms of the first update and of the change
after three rounds (``compare``)."""
from __future__ import annotations

import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import fedref
from chipbench.harness.common import CompileCounter, derive_seed
from chipbench.harness.trace import SPAN_PREFIX, WINDOW

WARM_ROUNDS = 3
TRACE_S = 20.0


class StopWindow(Exception):
    """Raised from ``plan()`` once the window has passed."""


def client_ops(mix: dict, seed: int) -> List[int]:
    """Every client's OP: the mix's counts per OP, in a seeded order."""
    ops = [int(op) for op, n in mix["ops"].items() for _ in range(n)]
    if len(ops) != mix["clients"]:
        raise ValueError(f"ops count {len(ops)} != clients {mix['clients']}")
    return [ops[i] for i in np.random.RandomState(seed).permutation(len(ops))]


class WindowPlanner:
    """The run's planner: fixed OPs, round marks, window and trace control.
    Has the ``Planner`` protocol of ``repro.fl.planner``."""

    def __init__(self, ops: List[int], seconds: float,
                 compiles: CompileCounter, trace_dir: Optional[str] = None):
        self.ops, self.seconds = list(ops), float(seconds)
        self.compiles, self.trace_dir = compiles, trace_dir
        self.captured: Dict[int, object] = {}
        self.t0 = self.t1 = None
        self.rounds = 0
        self.tracing = False
        self.traced: Dict[str, float] = {}
        self.window_compiles = None
        self._annos: list = []

    def begin(self, baseline_times) -> None:
        pass

    def feedback(self, times) -> None:
        pass

    def plan(self, round_idx, last_times, bandwidths):
        if round_idx <= WARM_ROUNDS:
            # the loop's global weights after ``round_idx`` rounds
            frame = sys._getframe(1)
            self.captured[round_idx] = jax.device_get(
                frame.f_locals["params"])
        if round_idx < WARM_ROUNDS:
            return list(self.ops)
        if round_idx == WARM_ROUNDS:
            if self.trace_dir is not None:
                jax.profiler.start_trace(self.trace_dir)
                self.tracing = True
                self._enter(WINDOW)
            self.before = self.compiles.snapshot()
            self.t0 = time.perf_counter()
        else:
            now = time.perf_counter()
            self._exit()                                   # the round
            if self.tracing and \
                    now - self.t0 >= min(TRACE_S, self.seconds):
                self._exit()                               # the traced part
                jax.profiler.stop_trace()
                self.tracing = False
                self.traced = {"rounds": round_idx - WARM_ROUNDS,
                               "window_s": now - self.t0}
            if now - self.t0 >= self.seconds:
                self.t1, self.rounds = now, round_idx - WARM_ROUNDS
                after = self.compiles.snapshot()
                self.window_compiles = {k: after[k] - self.before[k]
                                        for k in after}
                raise StopWindow()
        self._enter("round")
        return list(self.ops)

    def _enter(self, name: str) -> None:
        if not self.tracing:
            return
        a = jax.profiler.TraceAnnotation(
            name if name.startswith(SPAN_PREFIX) else SPAN_PREFIX + name)
        a.__enter__()
        self._annos.append(a)

    def _exit(self) -> None:
        if self._annos:
            self._annos.pop().__exit__(None, None, None)


# -----------------------------------------------------------------------------
# the comparison
# -----------------------------------------------------------------------------
def _leaves(tree) -> Dict[str, np.ndarray]:
    return {jax.tree_util.keystr(p): np.asarray(v, np.float32)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def _norms(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]
           ) -> Dict[str, float]:
    """Per-leaf norm of ``b - a``, accumulated in float64."""
    return {k: float(np.sqrt(np.sum(np.square(b[k].astype(np.float64)
                                              - a[k]))))
            for k in a}


def _gaps(prog: Dict[str, float], ref: Dict[str, float], keep) -> Dict:
    """Per leaf |‖Δprog‖ − ‖Δref‖| over the larger of ‖Δref‖ and the
    median leaf's ‖Δref‖."""
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def compare(ref, spec, test, prog: Dict[int, object], refs: List) -> dict:
    """The compared numbers, program against reference, with the worst
    leaf and the median leaf of each per-leaf gap."""
    p = {r: _leaves(prog[r]) for r in (0, 1, WARM_ROUNDS)}
    q = {r: _leaves(refs[r]) for r in (0, 1, WARM_ROUNDS)}
    if set(p[0]) != set(q[0]):
        raise ValueError("program and reference weights differ in structure")
    first = _norms(q[0], q[1])
    med = float(np.median(list(first.values())))
    # leaves whose first reference update is under a thousandth of the
    # median leaf's move by round-off alone (a bias before batch norm)
    keep = [k for k in q[0] if first[k] >= 1e-3 * med]
    out = {}
    for name, r in (("update", 1), ("change", WARM_ROUNDS)):
        gaps = _gaps(_norms(p[0], p[r]), _norms(q[0], q[r]), keep)
        worst = max(gaps, key=gaps.get)
        out[f"{name}_gap"] = gaps[worst]
        out[f"{name}_worst_leaf"] = worst
        out[f"{name}_median_gap"] = float(np.median(list(gaps.values())))
    data = {k: jnp.asarray(v) for k, v in test.items()}
    with jax.default_matmul_precision("highest"):
        ev = jax.jit(lambda w: ref.eval_loss(spec, w, data))
        gaps = []
        for r in range(1, WARM_ROUNDS + 1):
            lr_ = float(ev(refs[r]))
            gaps.append(abs(float(ev(prog[r])) - lr_) / abs(lr_))
    out.update(loss_gap=max(gaps), leaves_compared=len(keep),
               leaves=len(q[0]))
    return out


# -----------------------------------------------------------------------------
# one run
# -----------------------------------------------------------------------------
def fl_config(mix: dict, fl_seed: int):
    from repro.fl.loop import FLConfig
    return FLConfig(rounds=10 ** 9, local_iters=mix["local_iters"],
                    batch_size=mix["batch"], lr=mix["lr"],
                    lr_drop_round=10 ** 9, engine=mix["engine"],
                    delta_density=mix["delta_density"],
                    quantize_deltas=mix["quantize_deltas"],
                    quantize_transfer=mix["quantize_transfer"],
                    augment=mix["augment"], seed=fl_seed,
                    mesh_shape=(tuple(mix["mesh_shape"])
                                if mix.get("mesh_shape") else None))


def prepare(cell, seed: int) -> dict:
    """Data, OPs and seeds of one run (the same seed gives the same)."""
    clients, test = cell.config.make_data(cell.spec, cell.mix,
                                          derive_seed(seed, "data"))
    return {"clients": clients, "test": test,
            "ops": client_ops(cell.mix, derive_seed(seed, "ops")),
            "fl_seed": derive_seed(seed, "fl")}


def run(cell, seed: int, seconds: float, trace_dir: Optional[str],
        compiles: CompileCounter, t_start: float) -> dict:
    """One run of a training cell: set-up, window, then the comparison."""
    from repro.fl.loop import run_federated
    inputs = prepare(cell, seed)
    planner = WindowPlanner(inputs["ops"], seconds, compiles, trace_dir)
    fl = fl_config(cell.mix, inputs["fl_seed"])
    cfg = cell.config.program_config(cell.spec)
    try:
        run_federated(cfg, inputs["clients"], inputs["test"], fl,
                      planner=planner)
        raise RuntimeError("run_federated ended before the window closed")
    except StopWindow:
        pass
    from chipbench.harness.common import memory_peak_bytes
    peak = memory_peak_bytes()
    t_ref = time.perf_counter()
    refs = default_reference(cell, inputs)
    numbers = compare(cell.ref, cell.spec, inputs["test"], planner.captured,
                      refs)
    numbers["reference_s"] = time.perf_counter() - t_ref
    print(f"chipbench: reference took {numbers['reference_s']:.3f} s",
          file=sys.stderr, flush=True)
    window = planner.t1 - planner.t0
    K = cell.mix["clients"]
    return {"setup_s": planner.t0 - t_start,
            "round_s": window / planner.rounds,
            "rounds": planner.rounds, "window_s": window,
            "t0": planner.t0, "t1": planner.t1,
            "attempted": K * planner.rounds, "failed": 0,
            "memory_peak_bytes": peak, "numbers": numbers,
            "window_compiles": planner.window_compiles,
            "traced": planner.traced}


def default_reference(cell, inputs) -> List:
    return fedref.run(cell.ref, cell.spec, cell.mix, inputs["clients"],
                      inputs["ops"], inputs["fl_seed"],
                      rounds=WARM_ROUNDS).weights


def control_readings(cell, seed: int) -> dict:
    """The comparison's readings with the reference put in the program's
    place, computed in bfloat16 (the precision control) and with half of
    every batch left out, the mean taken over the rest (a planted fault)."""
    inputs = prepare(cell, seed)
    refs = default_reference(cell, inputs)
    args = (cell.ref, cell.spec, cell.mix, inputs["clients"], inputs["ops"],
            inputs["fl_seed"])
    bf16 = fedref.run(*args, rounds=WARM_ROUNDS, dtype=jnp.bfloat16,
                      precision="default").weights
    half = fedref.run(*args, rounds=WARM_ROUNDS, batch_fn=lambda b: {
        k: v[:len(v) // 2] for k, v in b.items()}).weights
    return {name: compare(cell.ref, cell.spec, inputs["test"],
                          dict(enumerate(ws)), refs)
            for name, ws in (("bf16", bf16), ("half_batch", half))}
