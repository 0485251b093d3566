"""Fleet scaling: simulation throughput of the local-training engines,
single-device and mesh-parallel.

The RL controller's whole point is fleet-scale re-planning (paper §IV), so
the simulator's rounds/sec at large K is the number that gates every
experiment.  This bench drives the fl/fleet.py engines directly — local
training + FedAvg aggregation, no planner/eval — and reports steady-state
seconds per round (one warm-up round excluded, so compile time is not
conflated with dispatch throughput) for K simulated clients:

* ``sequential`` — K x local_iters jit dispatches per round (pre-fleet loop)
* ``batched``    — one vmap-over-clients/scan-over-iters dispatch per
  OP-group chunk (fl/fleet.BatchedEngine)

Every (model, K) cell also grows a ``mesh`` row: the batched engine
re-timed 1-device vs a ``make_flat_mesh((devices, 1))`` data-axis mesh (the
shard_map fleet step), with 1-dev-vs-mesh equivalence flags.  The mesh
spans every device of an accelerator host, in this process; on CPU it spans
8 forced host devices in a ``--mesh-child`` subprocess, because the host
device count is fixed at jax import (``benchmarks.common.mesh_column``).

    PYTHONPATH=src python -m benchmarks.fleet_scaling           # full sweep
    PYTHONPATH=src python -m benchmarks.fleet_scaling --smoke   # CI: K=4 vgg

Caveat (important for interpreting CPU numbers): the batched engine's
per-client *weight gradients* lower to batched GEMMs / grouped convolutions
with the client axis as the batch dimension.  Accelerator backends execute
those as single large kernels; XLA *CPU* executes them as a serial loop
over clients, and the grouped-conv backward falls off a cliff superlinearly
in the client axis.  That cliff is exactly why the data-axis mesh wins for
the conv family even on a few-core host: each shard runs the plain
small-client-axis program, so 8 shards of G=1 beat one fused G=8 before
any core-level parallelism is counted.  For GEMM-bound LM families the
fused single-device chunk is already near-optimal on CPU and the mesh
column records an honest < 1 speedup.  The committed artifact's
``acceptance`` block asserts that at least one K >= 64 cell clears 1.0
(gated by tools/check_bench.py).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from benchmarks.common import mesh_column, mesh_devices, run_mesh_child
from repro.configs.lm_small import LM16M
from repro.configs.vgg import VGG5
from repro.data.loader import FleetLoader
from repro.data.synthetic import make_cifar_like, split_clients, token_dataset
from repro.fl.fedavg import fedavg_delta, fedavg_delta_stacked
from repro.fl.fleet import StackedRows, get_engine, take_rows
from repro.models.split_program import get_split_program

MODELS: Dict[str, dict] = {
    # IoT-sized local batches: fleet simulation is many small clients
    "vgg": dict(cfg=VGG5, batch=8, op=2, lr=0.01, per_client=16, seq=None),
    "lm_small": dict(cfg=LM16M, batch=2, op=3, lr=0.3, per_client=8,
                     seq=16),
}
KS = (4, 16, 64)
ITERS = 2            # truncated local round: keeps the K=64 cells tractable


def _client_data(name: str, spec: dict, K: int) -> List[dict]:
    n = K * spec["per_client"]
    if name == "vgg":
        return split_clients(make_cifar_like(n, seed=0), K)
    return split_clients(token_dataset(n, spec["seq"],
                                       spec["cfg"].vocab_size, seed=0), K)


def _bench_engine(engine_name: str, spec: dict, clients: List[dict], K: int,
                  rounds: int, iters: int, mesh=None,
                  return_params: bool = False):
    """Seconds per round, steady state (aggregation included).  With
    ``return_params`` also returns the warm-up round's averaged params for
    cross-engine equivalence flags (round 0 of the same seeded streams)."""
    program = get_split_program(spec["cfg"])
    params = program.init(jax.random.PRNGKey(0))
    agg_params = params        # default-device copy for the FedAvg glue:
    if mesh is not None:       # mesh-replicated params + device-0 delta rows
        params = program.shard_params(params, mesh)  # would mix device sets
    engine = get_engine(engine_name, program, iters, seed=0, augment=False,
                        quantize=False, mesh=mesh)
    loader = FleetLoader.for_clients(clients, spec["batch"], seed=0)
    ops = [spec["op"]] * K
    alive = list(range(K))

    def one_round(r: int):
        idxs, rows = engine.run_round(params, loader, ops, alive, r,
                                      spec["lr"])
        surv = take_rows(rows, list(range(len(idxs))))
        if isinstance(surv, StackedRows):
            new = fedavg_delta_stacked(agg_params, surv.tree)
        else:
            new = fedavg_delta(agg_params, surv)
        jax.block_until_ready(new)
        return new

    first = one_round(0)                   # warm-up: compile + caches
    t0 = time.perf_counter()
    for r in range(1, rounds + 1):
        one_round(r)
    s = (time.perf_counter() - t0) / rounds
    if return_params:
        return s, first
    return s


# -----------------------------------------------------------------------------
# mesh column (benchmarks.common.mesh_column)
# -----------------------------------------------------------------------------
def mesh_cell(name: str, spec: dict, clients: List[dict], K: int,
              rounds: int, iters: int, devices: int) -> Dict:
    """One (model, K) cell: batched engine 1-device vs the
    ``(devices, 1)`` data-axis mesh, plus equivalence flags from the
    round-0 averaged params of the two runs (bitwise is not promised at
    data > 1 — see docs/API.md — so ``allclose`` at fp32 tolerance is the
    gated flag)."""
    from repro.parallel.sharding import make_flat_mesh
    s1, p1 = _bench_engine("batched", spec, clients, K, rounds, iters,
                           return_params=True)
    mesh = make_flat_mesh((devices, 1))
    s8, p8 = _bench_engine("batched", spec, clients, K, rounds, iters,
                           mesh=mesh, return_params=True)
    a = [np.asarray(l) for l in jax.tree_util.tree_leaves(p1)]
    b = [np.asarray(l) for l in jax.tree_util.tree_leaves(p8)]
    return {
        "model": name, "K": K, "devices": devices,
        "s_per_round_1dev": round(s1, 4),
        "s_per_round_mesh": round(s8, 4),
        "speedup_mesh": round(s1 / s8, 3) if s8 else float("inf"),
        "mesh_bitwise": bool(all((x == y).all() for x, y in zip(a, b))),
        "mesh_allclose": bool(all(np.allclose(x, y, atol=1e-6)
                                  for x, y in zip(a, b))),
    }


def _mesh_cells(smoke: bool, devices: int) -> List[Dict]:
    """The mesh rows for the same (model, K) grid as ``run``."""
    cells = []
    for name, ks, rounds in _grid(smoke):
        spec = MODELS[name]
        for K in ks:
            clients = _client_data(name, spec, K)
            cell = mesh_cell(name, spec, clients, K, rounds, ITERS, devices)
            cells.append(cell)
            print(f"mesh {name} K={K:<4d} "
                  f"1dev={cell['s_per_round_1dev']:8.2f}s "
                  f"mesh={cell['s_per_round_mesh']:8.2f}s "
                  f"x{cell['speedup_mesh']} "
                  f"allclose={cell['mesh_allclose']}",
                  file=sys.stderr, flush=True)
    return cells


def _grid(smoke: bool):
    """(model, Ks, measured rounds) cells.  Smoke: vgg K=4 only (CI gate
    budget); full: both families over KS."""
    if smoke:
        return [("vgg", (4,), 1)]
    return [(name, KS, 1) for name in MODELS]


def run(smoke: bool = False, out_path: Optional[str] = None) -> Dict:
    from benchmarks.common import bench_out_path
    out_path = bench_out_path("fleet_scaling", smoke, out_path)
    results = []
    for name, ks, rounds in _grid(smoke):
        spec = MODELS[name]
        for K in ks:
            clients = _client_data(name, spec, K)
            secs = {}
            for eng in ("sequential", "batched"):
                if eng == "sequential" and K > 64:
                    continue
                secs[eng] = _bench_engine(eng, spec, clients, K, rounds,
                                          ITERS)
            for eng, s in secs.items():
                cell = {"model": name, "K": K, "engine": eng,
                        "s_per_round": round(s, 4),
                        "rounds_per_s": round(1.0 / s, 4)}
                if eng == "batched" and "sequential" in secs:
                    cell["speedup_vs_sequential"] = round(
                        secs["sequential"] / s, 3)
                results.append(cell)
                print(f"{name} K={K:<4d} {eng:<10s} {s:8.2f} s/round",
                      flush=True)
    mesh = mesh_column("benchmarks.fleet_scaling", smoke,
                       lambda n: _mesh_cells(smoke, n)) or []
    payload = {"backend": jax.default_backend(), "smoke": smoke,
               "mesh_devices": mesh_devices(), "local_iters": ITERS,
               "results": results, "mesh": mesh}
    if not smoke and mesh:
        # the ISSUE 10 acceptance cell, recorded in the committed artifact
        # and gated by tools/check_bench.py: at least one K >= 64 mesh row
        # beats the 1-device batched engine
        big = [c for c in mesh if c["K"] >= 64]
        best = max(big, key=lambda c: c["speedup_mesh"])
        payload["acceptance"] = {
            "mesh_beats_1dev_at_K64": bool(best["speedup_mesh"] > 1.0),
            "best": best,
        }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out_path}")
    return payload


def bench_fleet_scaling():
    """benchmarks/run.py hook: smoke sweep, CSV-derived summary."""
    payload = run(smoke=True)
    batched = [c for c in payload["results"] if c["engine"] == "batched"]
    m = payload["mesh"][0] if payload["mesh"] else {}
    return 0.0, (f"{len(payload['results'])} engine cells; batched "
                 f"{batched[0]['s_per_round']:.2f} s/round @K="
                 f"{batched[0]['K']}; mesh({payload['mesh_devices']},1) "
                 f"x{m.get('speedup_mesh')} vs 1-dev "
                 f"(allclose={m.get('mesh_allclose')})")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: vgg K=4 only")
    ap.add_argument("--out", default=None,
                    help="output JSON (default: BENCH_fleet_scaling.json, "
                         "or benchmarks/_smoke/ under --smoke)")
    ap.add_argument("--mesh-child", action="store_true",
                    help="internal: emit the mesh rows (spawned on CPU by "
                         "the parent with forced host devices)")
    args = ap.parse_args()
    if args.mesh_child:
        run_mesh_child(lambda n: _mesh_cells(args.smoke, n))
    else:
        run(smoke=args.smoke, out_path=args.out)


if __name__ == "__main__":
    main()
