"""Shared pieces of the benchmark: finding a cell's files by name, seeds,
the device check, compile counting and the result line."""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Dict, List

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]      # chipbench/
REPO = BENCH_DIR.parent                               # the checkout


class BenchError(Exception):
    """A run that cannot produce a result (no chip, missing files)."""


# -----------------------------------------------------------------------------
# finding things by name
# -----------------------------------------------------------------------------
def load_module(path: Path):
    """Import a Python file by path (file names may hold '.' and '-')."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    modname = "chipbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    return json.loads(path.read_text())


def load_benchmark() -> dict:
    return load_json(REPO / "BENCHMARK.json")


class Cell:
    """One workload of BENCHMARK.json with every file it names loaded."""

    def __init__(self, bench: dict, name: str):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise BenchError(f"no workload {name!r}; known: {sorted(by_name)}")
        self.workload = by_name[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config_name = conf["name"]
        self.spec = load_json(REPO / conf["file"])
        self.config = load_module(BENCH_DIR / "configs"
                                  / f"{conf['name']}.py")
        self.ref = load_module(BENCH_DIR / "configs"
                               / f"{conf['name']}.ref.py")
        self.mix = load_json(BENCH_DIR / "traffic"
                             / f"{self.workload['traffic']}.json")
        limits = BENCH_DIR / "cells" / f"{name}.json"
        self.limits = load_json(limits)["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        # a per-layer metric without a cell list belongs to every cell that
        # reports the end-to-end metric it moves
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in reported)]
        self.readers = {m["name"]: load_module(BENCH_DIR / "metrics"
                                               / f"{m['name']}.py")
                        for m in self.per_layer}


# -----------------------------------------------------------------------------
# seeds: the driver's seeds reach past 32 signed bits; every generator here
# takes a 31-bit seed derived from it and a tag
# -----------------------------------------------------------------------------
def derive_seed(seed: int, tag: str) -> int:
    words = [int(b) for b in tag.encode()]
    ss = np.random.SeedSequence([int(seed) % (1 << 63)] + words)
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


# -----------------------------------------------------------------------------
# device, compile counting
# -----------------------------------------------------------------------------
def device_info(chips: int, allow_cpu: bool = False) -> dict:
    """The platform JAX sees; no accelerator, or fewer chips than the cell
    asks for, is an error (the CPU only for the in-process rehearsal)."""
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu" and not allow_cpu:
        raise BenchError(f"no TPU: JAX sees {plat}; the benchmark measures "
                         f"the chip and does not fall back")
    if plat == "tpu" and len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": plat, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """Lowerings and backend compiles seen through ``jax.monitoring``; a
    persistent-cache hit lowers but does not backend-compile."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        self.counts = {"lowered": 0, "compiled": 0}

    def install(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, duration: float, **_) -> None:
        kind = self.EVENTS.get(event)
        if kind:
            self.counts[kind] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


def memory_peak_bytes() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# -----------------------------------------------------------------------------
# the result line
# -----------------------------------------------------------------------------
def emit(result: dict, checks: List[dict], out=None, err=None) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as one JSON line on standard output,
    with the checks under the last key."""
    out = out or sys.stdout
    err = err or sys.stderr
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=err, flush=True)
    line = dict(result)
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    print(json.dumps(line), file=out, flush=True)


def check(name: str, value: float, limit: float) -> dict:
    """A compared number passes when finite and at or under its limit."""
    value = float(value)
    return {"name": name, "value": value, "limit": float(limit),
            "ok": bool(math.isfinite(value) and value <= limit)}
