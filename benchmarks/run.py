"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``derived`` carries the validation
against the paper's own numbers (or the roofline summary for the dry-run-
derived benches).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig5,table5
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import paper_validation as pv
from benchmarks.async_vs_sync import bench_async_vs_sync
from benchmarks.fleet_scaling import bench_fleet_scaling
from benchmarks.hetero import bench_hetero
from benchmarks.hierarchy import bench_hierarchy
from benchmarks.server_step import bench_server_step
from benchmarks.serving import bench_serving
from repro.compile_cache import enable_compile_cache


def bench_roofline():
    from benchmarks import roofline
    t0 = time.time()
    rows = roofline.table()
    us = (time.time() - t0) * 1e6
    if not rows:
        return us, "no dry-run artifacts yet (run repro.launch.dryrun --all)"
    doms = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    worst = min(rows, key=lambda r: r["roofline_fraction_mfu"])
    best = max(rows, key=lambda r: r["roofline_fraction_mfu"])
    return us, (f"{len(rows)} cells; dominant={doms}; "
                f"best MFU-bound={best['roofline_fraction_mfu']:.2f} "
                f"({best['arch']}/{best['shape']}); "
                f"worst={worst['roofline_fraction_mfu']:.2f} "
                f"({worst['arch']}/{worst['shape']})")


def bench_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.topk_compress.ops import topk_compress
    from repro.kernels.quant_transfer.ops import quantize
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 128, 4, 64))
    k = jax.random.normal(key, (1, 128, 2, 64))
    x = jax.random.normal(key, (1, 128, 4, 32))
    dt = jax.nn.softplus(jax.random.normal(key, (1, 128, 4)))
    A = -jnp.ones((4,))
    Bm = jax.random.normal(key, (1, 128, 32))
    g = jax.random.normal(key, (8192,))
    act = jax.random.normal(key, (256, 512))
    names = []
    for name, fn in [
        ("flash_attention", lambda: flash_attention(q, k, k)),
        ("ssd_scan", lambda: ssd_scan(x, dt, A, Bm, Bm, chunk=64)),
        ("topk_compress", lambda: topk_compress(g, 16, 1024)),
        ("quant_transfer", lambda: quantize(act)),
    ]:
        fn()  # warm (compile)
        t0 = time.time()
        jax.block_until_ready(fn())
        names.append(f"{name}={1e3*(time.time()-t0):.0f}ms")
    from repro.kernels.compat import default_interpret
    mode = "interpret (CPU correctness mode)" if default_interpret() \
        else "compiled"
    return 0.0, (f"{mode} timings via kernels/compat backend resolution: "
                 + " ".join(names))


BENCHES = {
    # paper tables/figures (validation against the paper's numbers)
    "table5": pv.bench_table5,
    "table6": pv.bench_table6,
    "table7": pv.bench_table7,
    "table8": pv.bench_table8,
    "table9": pv.bench_table9,
    "fig5": pv.bench_fig5,
    "fig5_factored": pv.bench_fig5_factored,
    "fig7": pv.bench_fig7,
    "fig6": pv.bench_fig6,
    "fig10": pv.bench_fig10,
    "fig8": pv.bench_fig8,
    "fig11": pv.bench_fig11,
    "fig9": pv.bench_fig9,
    "quant_transport": pv.bench_quant_transport,
    "overhead": pv.bench_overhead,
    # beyond-paper scenarios
    "noniid": pv.bench_noniid,
    "async_vs_sync": bench_async_vs_sync,
    "hetero": bench_hetero,
    "hierarchy": bench_hierarchy,
    "fleet_scaling": bench_fleet_scaling,
    "server_step": bench_server_step,
    "serving": bench_serving,
    # system benches
    "roofline": bench_roofline,
    "kernels": bench_kernels,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()
    selected = ([s.strip() for s in args.only.split(",") if s.strip()]
                or list(BENCHES))
    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        try:
            us, derived = BENCHES[name]()
            print(f"{name},{us:.1f},\"{derived}\"", flush=True)
        except Exception as e:
            failures += 1
            print(f"{name},-1,\"ERROR {type(e).__name__}: {e}\"", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
