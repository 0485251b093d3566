"""Shared benchmark fixtures: the paper's measured tables, the calibrated
simulated testbed, and helpers for timing + CSV emission + artifact
routing (full artifacts at the repo root, smoke artifacts under the
gitignored ``benchmarks/_smoke/``)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.configs.vgg import VGG5, VGG8, VGGConfig
from repro.core import costmodel as cm

# --- the paper's measured numbers + calibrated testbed ----------------------
from repro.core.testbed import (  # noqa: F401
    TABLE_V,
    TABLE_VI,
    TABLE_VII_TIMES,
    TABLE_VIII,
    paper_testbed,
    server_calibration,
)

# optimal per-group action ranges, §V-B (G3 = low-bandwidth group: at
# 10 Mbps the optimum for VGG-5 is *native* — Table V last column)
PAPER_OPTIMAL_ACTIONS = {"G1": (0.96, 1.0), "G2": (0.0, 0.38),
                         "G3": (0.0, 0.38)}
LOW_BW_OPTIMAL = (0.96, 1.0)
PAPER_BOUNDARIES = (0.38, 0.79, 0.96)


def calibrated_workload(cfg: VGGConfig = VGG5, batch: int = 100
                        ) -> cm.Workload:
    return cm.vgg_workload(cfg, batch_size=batch)


def bench_out_path(name: str, smoke: bool,
                   override: Optional[str] = None) -> str:
    """Where a benchmark's JSON artifact goes.  Full runs keep the
    committed ``BENCH_<name>.json`` at the repo root; ``--smoke`` runs are
    CI throwaways and land in the gitignored ``benchmarks/_smoke/``
    (anchored at this file, not the cwd).  ``override`` (the ``--out``
    flag) wins outright."""
    if override:
        return override
    if smoke:
        d = Path(__file__).resolve().parent / "_smoke"
        d.mkdir(exist_ok=True)
        return str(d / f"BENCH_{name}.json")
    return f"BENCH_{name}.json"


# forced host devices behind a mesh column on CPU
HOST_MESH_DEVICES = 8


def mesh_devices() -> int:
    """Devices a bench's mesh column spans: every visible accelerator
    device, or ``HOST_MESH_DEVICES`` forced host devices on CPU."""
    import jax
    if jax.default_backend() == "cpu":
        return HOST_MESH_DEVICES
    return len(jax.devices())


def mesh_column(module: str, smoke: bool, cells: Callable[[int], object]):
    """A bench's mesh column: ``cells(devices)`` over ``mesh_devices()``.

    It runs in this process whenever the process sees that many devices
    (an accelerator host, or a CPU process started with the host-device
    flag).  A plain CPU process cannot add devices after JAX is imported,
    so there it runs ``python -m <module> --mesh-child`` with ``XLA_FLAGS``
    forcing the host devices and parses the child's ``MESH_JSON`` line.
    A single accelerator has no mesh: the column is ``None``."""
    import jax
    n = mesh_devices()
    if n > 1 and len(jax.devices()) >= n:
        return cells(n)
    if jax.default_backend() != "cpu":
        return None
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    cmd = [sys.executable, "-m", module, "--mesh-child"]
    if smoke:
        cmd.append("--smoke")
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         cwd=str(Path(__file__).resolve().parents[1]),
                         timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(f"mesh child failed:\n{out.stderr[-4000:]}")
    for line in out.stdout.splitlines():
        if line.startswith("MESH_JSON:"):
            return json.loads(line[len("MESH_JSON:"):])
    raise RuntimeError(f"mesh child emitted no MESH_JSON line:\n"
                       f"{out.stdout[-2000:]}")


def run_mesh_child(cells: Callable[[int], object]) -> None:
    """``--mesh-child`` body: print ``cells(HOST_MESH_DEVICES)`` on one
    ``MESH_JSON`` line for ``mesh_column`` in the parent."""
    import jax
    assert len(jax.devices()) >= HOST_MESH_DEVICES, (
        "run via the parent, which sets XLA_FLAGS="
        f"--xla_force_host_platform_device_count={HOST_MESH_DEVICES}")
    print("MESH_JSON:" + json.dumps(cells(HOST_MESH_DEVICES)))


class Csv:
    """Collects ``name,us_per_call,derived`` rows (benchmarks/run.py format)."""

    def __init__(self):
        self.rows: List[Tuple[str, float, str]] = []

    def add(self, name: str, us_per_call: float, derived: str):
        self.rows.append((name, us_per_call, derived))

    def format_row(self, i: int = -1) -> str:
        name, us, derived = self.rows[i]
        return f"{name},{us:.1f},{derived}"

    def emit(self):
        for i in range(len(self.rows)):
            print(self.format_row(i))


def timed(fn, *args, repeats: int = 1, **kw):
    t0 = time.time()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.time() - t0) / repeats
    return out, dt * 1e6   # us
