"""Run one cell's plain reference alone in this process, on the stacked or
the streamed round, and print its device peak; with ``--against``, also
compare its weights with those that a run on the other path saved.

    python3 chipbench/reference_memory.py --workload <name> --seed <n> \\
        --path <stacked|streamed> --save <file.npz> [--against <file.npz>]

A process's device peak never falls, so each path runs in a process of its
own: first one path with ``--save``, then the other with ``--against``.  The
comparison is the benchmark's own (``harness/train.py`` ``compare``), with
the saved run in the reference's place, beside the largest elementwise gap
of each leaf over the leaf's largest magnitude.  Without a TPU the run exits
with code 2 and prints no result; the last line of standard output is the
result as one JSON object."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
# the path forced by the limit the round is given: any footprint fits under
# the first, none under the second
LIMITS = {"stacked": 2 ** 62, "streamed": 0}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--path", choices=sorted(LIMITS), required=True)
    ap.add_argument("--save")
    ap.add_argument("--against")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    import jax
    import numpy as np
    from chipbench.harness import common, fedref, train
    try:
        cell = common.Cell(common.load_benchmark(), args.workload)
        device = common.device_info(cell.chips)
    except common.BenchError as e:
        print(f"reference_memory: {e}", file=sys.stderr)
        return 2
    inputs = train.prepare(cell, args.seed)
    t0 = time.perf_counter()
    ref = fedref.run(cell.ref, cell.spec, cell.mix, inputs["clients"],
                     inputs["ops"], inputs["fl_seed"],
                     rounds=train.WARM_ROUNDS, limit=LIMITS[args.path])
    out = {"workload": args.workload, "seed": args.seed, "path": ref.path,
           "reference_s": time.perf_counter() - t0,
           "peak_bytes_in_use": common.memory_peak_bytes(),
           "bytes_limit": fedref.device_bytes_limit(), "device": device}
    treedef = jax.tree_util.tree_structure(ref.weights[0])
    if args.save:
        np.savez(args.save, **{f"{r}_{i}": a for r, w in
                               enumerate(ref.weights)
                               for i, a in enumerate(jax.tree_util
                                                     .tree_leaves(w))})
    if args.against:
        with np.load(args.against) as f:
            other = [jax.tree_util.tree_unflatten(treedef, [
                f[f"{r}_{i}"] for i in range(treedef.num_leaves)])
                for r in range(len(ref.weights))]
        numbers = train.compare(cell.ref, cell.spec, inputs["test"],
                                dict(enumerate(ref.weights)), other)
        gaps = [float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
                for a, b in zip(jax.tree_util.tree_leaves(ref.weights[-1]),
                                jax.tree_util.tree_leaves(other[-1]))]
        numbers.update(max_elementwise_gap=max(gaps),
                       leaves_equal=sum(g == 0.0 for g in gaps))
        out["against"] = numbers
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
