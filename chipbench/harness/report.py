"""One cell's run turned into its result line: end-to-end metrics
(``--trace 0``) or per-layer metrics from the trace (``--trace 1``), the
device, and every compared number beside its limit."""
from __future__ import annotations

import json
import sys
from types import SimpleNamespace
from typing import List, Tuple

from chipbench.harness import common, train
from chipbench.harness.common import BENCH_DIR


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "harness" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r}: add it to "
                       f"harness/peaks.json with its source")
    return table["devices"][kind]


def run_cell(cell, args, device: dict, compiles, trace_dir,
             t_start: float) -> Tuple[dict, List[dict]]:
    kind = cell.mix["kind"]
    if kind != "federated":
        raise ValueError(f"unknown traffic kind {kind!r}")
    out = train.run(cell, args.seed, args.seconds, trace_dir, compiles,
                    t_start)
    numbers = out["numbers"]
    checks = [common.check(name, numbers[name], limit)
              for name, limit in sorted(cell.limits.items())]
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": out["attempted"], "failed": out["failed"]}
    on_chip = device["platform"] == "tpu"
    if not on_chip:
        # the in-process rehearsal: a CPU timing is no chip metric
        result["metrics"] = {}
    elif trace_dir is None:
        result["metrics"] = {
            m["name"]: {"value": out[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    else:
        from chipbench.harness import trace as tr
        data = tr.load(trace_dir)
        # the readers see the traced part of the window
        ctx = SimpleNamespace(cell=cell, out=dict(out, **out["traced"]),
                              trace=data, peaks=peaks_for(device["kind"]))
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        dev.update(busy_s=data.busy_s(), window_s=data.window_s)
        result["breakdown"] = {"device_ops": data.top_ops(10),
                               "idle_gaps": data.idle_gaps(10)}
    result["device"] = dev
    extra = {"window_compiles": out["window_compiles"],
             "numbers": numbers, "rounds": out["rounds"]}
    if trace_dir is not None:
        extra["traced_rounds"] = out["traced"]["rounds"]
    if getattr(args, "control", 0):
        extra["control"] = train.control_readings(cell, args.seed)
    print("chipbench: " + json.dumps(extra), file=sys.stderr, flush=True)
    result["run"] = extra
    return result, checks
