"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic mix, per-layer metrics and limits are
found by name from BENCHMARK.json (see chipbench/harness/common.py).  A run
makes its inputs and weights from ``--seed``, warms up every program the
window calls (set-up), measures for ``--seconds``, and then checks what the
window produced against the plain reference.  ``--trace 1`` records the
device trace of the window's first rounds (``harness/train.py``
``TRACE_S``) and reports the per-layer metrics instead of the end-to-end
ones.  ``--control 1`` also reports the readings of the reference computed
in bfloat16, and with half of each batch left out, in the program's place,
for setting limits.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.  The last line of standard output is the
result as one JSON object; the compared numbers and their limits are the
last lines of standard error and the last key of that object."""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, allow_cpu: bool = False, patch=None) -> int:
    """``allow_cpu`` and ``patch`` (a callable applied to the loaded cell)
    exist for the in-process rehearsal of the tests only."""
    args = parse(argv)
    for p in (str(REPO / "src"), str(REPO)):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        from chipbench.harness import common
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"chipbench: cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        return 2
    try:
        cell = common.Cell(common.load_benchmark(), args.workload)
        device = common.device_info(cell.chips, allow_cpu=allow_cpu)
    except common.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if patch is not None:
        patch(cell)
    if device["platform"] == "tpu":
        import jax
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        # cache every program, however fast it compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = common.CompileCounter().install()
    from chipbench.harness import report
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        result, checks = report.run_cell(cell, args, device, compiles,
                                         trace_dir, T_START)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
