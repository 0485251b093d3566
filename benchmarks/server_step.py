"""Server-step latency: fused flat-buffer pipeline vs per-leaf reference.

The fused path (fl/flatbuf.py) runs one round of server work — stack
survivor deltas, error-feedback top-k, optional int8, weighted reduce,
apply — as a constant number of jitted dispatches (rows_to_deltas +
ServerStep + unflatten = 3), where the reference per-leaf tree_map path
issues O(K x leaves) jnp ops.  This bench measures steady-state
aggregation wall-clock for K in {4, 16, 64, 256} over two scenarios
(plain weighted averaging; top-k error feedback + int8 wire format) and
emits machine-readable ``BENCH_server_step.json``.  Each cell also grows
a ``mesh`` column: the same fused round timed on 1 device vs a
``make_flat_mesh((1, devices))`` mesh via ``ShardedFlatLayout`` /
``ShardedServerStep``, with per-cell sharded-vs-reference equivalence
flags (``sharded_bitwise`` / ``sharded_allclose``).  The mesh spans every
device of an accelerator host, in this process; on CPU it spans 8 forced
host devices in a ``--mesh-child`` subprocess, because the host device
count is fixed at jax import (``benchmarks.common.mesh_column``).

    PYTHONPATH=src python -m benchmarks.server_step           # full sweep
    PYTHONPATH=src python -m benchmarks.server_step --smoke   # CI: K=4 only

Dispatch accounting: ``fused_dispatches`` is exact by construction (the
three jitted entry points invoked per round; ``ServerStep.calls`` is
asserted to advance by one).  ``reference_dispatch_floor`` is the K x
leaves lower bound on the reference path's per-leaf op dispatches (each
leaf additionally issues several jnp calls, so the true count is a small
multiple).  Timings on CPU run the Pallas kernels in interpreter mode
(kernels/compat.py); accelerator backends compile them, widening the gap.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import mesh_column, mesh_devices, run_mesh_child
from repro.configs import get_smoke_config
from repro.configs.vgg import VGG5
from repro.fl.flatbuf import get_server_step, reference_server_step
from repro.fl.loop import _delta_trees
from repro.models.split_program import get_split_program

KS = (4, 16, 64, 256)
# skip (model, K) cells whose stacked delta matrix would not fit comfortably
MAX_STACK_BYTES = 512 * 1024 ** 2
SCENARIOS = {
    "avg": dict(density=1.0, quantize=False),
    "topk_int8": dict(density=0.01, quantize=True),
}


def _client_rows(program, params, K: int) -> List:
    """K perturbed parameter sets (what the fleet engines hand back)."""
    keys = jax.random.split(jax.random.PRNGKey(1), K)
    return [jax.tree_util.tree_map(
        lambda p, kk=k: p + 0.01 * jax.random.normal(kk, p.shape,
                                                     jnp.float32),
        params) for k in keys]


def _time(fn, reps: int) -> float:
    jax.block_until_ready(fn())            # warm / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())        # every rep fully retired
    return (time.perf_counter() - t0) / reps * 1e3   # ms


def bench_cell(program, params, K: int, density: float, quantize: bool,
               reps: int) -> Dict:
    layout = program.flat_layout(params)
    rows = _client_rows(program, params, K)
    weights = list(np.arange(1, K + 1, dtype=np.float64))
    track = density < 1.0
    err = jnp.zeros((K, layout.padded), jnp.float32) if track else None
    all_ids = jnp.arange(K, dtype=jnp.int32)
    g_flat = layout.flatten(params)
    step = get_server_step(layout, density, quantize)

    def fused_round():
        deltas = layout.rows_to_deltas(rows, g_flat)
        # gather the error rows like the real loops do — a fresh buffer per
        # round, required because ServerStep donates them
        err_rows = None if err is None else err[all_ids]
        new_g, new_err = step(g_flat, deltas, weights, err_rows)
        return layout.unflatten(new_g), new_err

    def reference_round():
        return reference_server_step(
            layout, params, _delta_trees(params, rows), weights, err,
            density=density, quantize=quantize)

    calls0 = step.calls
    fused_ms = _time(fused_round, reps)
    assert step.calls == calls0 + reps + 1   # ONE ServerStep dispatch/round
    ref_ms = _time(reference_round, reps)
    leaves = len(layout.shapes)
    return {
        "K": K, "n_params": layout.size, "padded": layout.padded,
        "leaves": leaves, "density": density, "quantize": quantize,
        "ref_ms": round(ref_ms, 3), "fused_ms": round(fused_ms, 3),
        "speedup": round(ref_ms / fused_ms, 2) if fused_ms else float("inf"),
        "fused_dispatches": 3,
        "reference_dispatch_floor": K * leaves,
    }


def _bench_models(smoke: bool):
    models = [("vgg5", VGG5)]
    if not smoke:
        models.append(("llama3-8b-smoke", get_smoke_config("llama3-8b")))
    return models


def mesh_cell(program, params, K: int, density: float, quantize: bool,
              reps: int, devices: int) -> Dict:
    """One (model, K, scenario) cell timed 1-device vs ``devices``-device,
    with sharded-vs-reference equivalence flags.  Must run in a process
    that sees ``devices`` devices (``benchmarks.common.mesh_column``)."""
    from repro.parallel.sharding import make_flat_mesh
    base = program.flat_layout(params)
    lay = program.flat_layout(params,
                              mesh=make_flat_mesh((1, devices)))
    rows = _client_rows(program, params, K)
    weights = list(np.arange(1, K + 1, dtype=np.float64))
    track = density < 1.0
    step1 = get_server_step(base, density, quantize)
    step8 = get_server_step(lay, density, quantize)
    g1 = base.flatten(params)
    g8 = lay.flatten(params)

    def round_on(layout, g, step):
        def fn():
            # fresh delta and error rows every round: the step donates both
            deltas = layout.rows_to_deltas(rows, g)
            err = (jnp.zeros((K, layout.padded), jnp.float32) if track
                   else None)
            return step(g, deltas, weights, err)
        return fn

    one = round_on(base, g1, step1)
    eight = round_on(lay, g8, step8)
    ms1 = _time(lambda: one()[0], reps)
    ms8 = _time(lambda: eight()[0], reps)
    ref_g = np.asarray(one()[0])
    new_g = np.asarray(eight()[0])[:base.padded]
    return {
        "devices": devices,
        "fused_ms_1dev": round(ms1, 3),
        "fused_ms_8dev": round(ms8, 3),
        "speedup_8dev": round(ms1 / ms8, 2) if ms8 else float("inf"),
        "sharded_bitwise": bool((new_g == ref_g).all()),
        "sharded_allclose": bool(np.allclose(new_g, ref_g, atol=1e-6)),
    }


def _mesh_cells(smoke: bool, devices: int) -> Dict[str, Dict]:
    """The mesh column for the same cell grid as ``run``."""
    ks = (4,) if smoke else KS
    reps = 1
    cells = {}
    for name, cfg in _bench_models(smoke):
        program = get_split_program(cfg)
        params = program.init(jax.random.PRNGKey(0))
        layout = program.flat_layout(params)
        for K in ks:
            if K * layout.padded * 4 > MAX_STACK_BYTES:
                continue
            for scen, kw in SCENARIOS.items():
                if smoke and scen != "avg":
                    continue
                cell = mesh_cell(program, params, K, reps=reps,
                                 devices=devices, **kw)
                cells[f"{name}|{K}|{scen}"] = cell
                print(f"mesh {name} K={K:<4d} {scen:<10s} "
                      f"1dev={cell['fused_ms_1dev']:8.1f}ms "
                      f"{devices}dev={cell['fused_ms_8dev']:8.1f}ms "
                      f"x{cell['speedup_8dev']} "
                      f"bitwise={cell['sharded_bitwise']}",
                      file=sys.stderr, flush=True)
    return cells


def run(smoke: bool = False, out_path: str = None) -> Dict:
    # smoke runs must not clobber the recorded full-sweep artifact: they
    # land in the gitignored benchmarks/_smoke/
    from benchmarks.common import bench_out_path
    out_path = bench_out_path("server_step", smoke, out_path)
    models = _bench_models(smoke)
    ks = (4,) if smoke else KS
    reps = 1 if smoke else 2
    results = []
    for name, cfg in models:
        program = get_split_program(cfg)
        params = program.init(jax.random.PRNGKey(0))
        layout = program.flat_layout(params)
        for K in ks:
            if K * layout.padded * 4 > MAX_STACK_BYTES:
                results.append({"model": name, "K": K,
                                "skipped": "stacked deltas exceed "
                                           f"{MAX_STACK_BYTES >> 20} MiB"})
                continue
            for scen, kw in SCENARIOS.items():
                if smoke and scen != "avg":
                    continue
                cell = bench_cell(program, params, K, reps=reps, **kw)
                cell.update(model=name, scenario=scen)
                results.append(cell)
                print(f"{name} K={K:<4d} {scen:<10s} "
                      f"ref={cell['ref_ms']:8.1f}ms "
                      f"fused={cell['fused_ms']:8.1f}ms "
                      f"x{cell['speedup']}", flush=True)
    # mesh column: re-time every cell 1-device vs the mesh, and record
    # sharded-vs-reference equivalence
    mesh = mesh_column("benchmarks.server_step", smoke,
                       lambda n: _mesh_cells(smoke, n)) or {}
    for cell in results:
        if "skipped" in cell:
            continue
        cell["mesh"] = mesh.get(
            f"{cell['model']}|{cell['K']}|{cell['scenario']}")
    payload = {"backend": jax.default_backend(), "smoke": smoke,
               "mesh_devices": mesh_devices(), "results": results}
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out_path}")
    return payload


def bench_server_step():
    """benchmarks/run.py hook: tiny sweep, CSV-derived summary."""
    payload = run(smoke=True)
    cells = [c for c in payload["results"] if "speedup" in c]
    best = max(cells, key=lambda c: c["speedup"])
    return 0.0, (f"{len(cells)} cells; fused=3 dispatches/round vs "
                 f"reference floor K*leaves; best speedup x{best['speedup']} "
                 f"({best['model']} K={best['K']} {best['scenario']})")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: K=4, averaging scenario only")
    ap.add_argument("--out", default=None,
                    help="output JSON (default: BENCH_server_step.json, "
                         "or benchmarks/_smoke/ under --smoke)")
    ap.add_argument("--mesh-child", action="store_true",
                    help="internal: emit the mesh column (spawned on CPU by "
                         "the parent with forced host devices)")
    args = ap.parse_args()
    if args.mesh_child:
        run_mesh_child(lambda n: _mesh_cells(args.smoke, n))
    else:
        run(smoke=args.smoke, out_path=args.out)
