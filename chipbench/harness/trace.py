"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists of events; everything after that works on those lists, so the tests
check it on a small recorded trace without a chip:

* device events: per device plane, the ``XLA Ops`` line (one event per
  operation that ran, with its XLA module in the ``hlo_module`` stat) and
  the ``XLA Modules`` line (one event per executable run);
* host events: the events of the host thread that runs the harness,
  among them its own spans, which it writes with ``jax.profiler.TraceAnnotation`` under the
  ``chipbench:`` prefix; ``chipbench:window`` marks the measured window.

Busy time is the union of the op intervals of a device inside the window,
and the idle share is one minus busy over the window, averaged over the
devices used.  Idle gaps are named by the innermost host event open at the
middle of the gap.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW = "chipbench:window"
SPAN_PREFIX = "chipbench:"

Event = Tuple[str, float, float, str]      # name, start_ns, dur_ns, module


class TraceData:
    """Events of one traced window (``from_json`` reads a recorded one)."""

    def __init__(self, devices: Dict[str, Dict[str, List[Event]]],
                 host: List[Event]):
        self.devices = devices          # plane -> {"ops": [...], "modules"}
        self.host = host
        win = [e for e in host if e[0] == WINDOW]
        if not win:
            raise ValueError("trace holds no chipbench:window span")
        self.t0 = win[0][1]
        self.t1 = win[0][1] + win[0][2]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @classmethod
    def from_json(cls, d: dict) -> "TraceData":
        devices = {p: {k: [tuple(e) for e in v] for k, v in lines.items()}
                   for p, lines in d["devices"].items()}
        return cls(devices, [tuple(e) for e in d["host"]])

    # -- busy and idle -------------------------------------------------------
    def _clip(self, events: List[Event]) -> List[Tuple[float, float]]:
        out = []
        for _, s, d, _ in events:
            a, b = max(s, self.t0), min(s + d, self.t1)
            if b > a:
                out.append((a, b))
        return out

    def busy_intervals(self, plane: str) -> List[Tuple[float, float]]:
        """Merged intervals in which some operation ran on ``plane``."""
        iv = sorted(self._clip(self.devices[plane]["ops"]))
        merged: List[List[float]] = []
        for a, b in iv:
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the device planes."""
        planes = list(self.devices)
        if not planes:
            return 0.0
        tot = sum(sum(b - a for a, b in self.busy_intervals(p))
                  for p in planes)
        return tot / len(planes) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    # -- time by name ----------------------------------------------------------
    def _sum(self, line: str, pattern: str, key: int) -> float:
        rx = re.compile(pattern)
        tot = 0.0
        for lines in self.devices.values():
            for a, b in self._clip([e for e in lines[line]
                                    if rx.search(e[key])]):
                tot += b - a
        return tot * 1e-9 / max(len(self.devices), 1)

    def module_s(self, pattern: str) -> float:
        """Device seconds of executables whose module name matches."""
        return self._sum("modules", pattern, 0)

    def op_s(self, pattern: str) -> float:
        """Device seconds of operations whose name matches (kernels)."""
        return self._sum("ops", pattern, 0)

    # -- breakdown -------------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[list]:
        """Operations by device seconds in the window (averaged over the
        device planes), largest first."""
        tot: Dict[str, float] = {}
        for lines in self.devices.values():
            for e in lines["ops"]:
                a, b = max(e[1], self.t0), min(e[1] + e[2], self.t1)
                if b > a:
                    tot[e[0]] = tot.get(e[0], 0.0) + (b - a)
        k = max(len(self.devices), 1)
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s * 1e-9 / k] for name, s in rows]

    def _host_labels(self, times: List[float]) -> List[str]:
        """Innermost host event open at each of the sorted ``times``.  Events
        of one thread nest, so one sweep with a stack finds them all."""
        evs = sorted((e for e in self.host if e[0] != WINDOW),
                     key=lambda e: (e[1], -e[2]))
        stack: List[Event] = []
        labels, i = [], 0
        for t in times:
            while i < len(evs) and evs[i][1] <= t:
                while stack and stack[-1][1] + stack[-1][2] <= evs[i][1]:
                    stack.pop()
                stack.append(evs[i])
                i += 1
            while stack and stack[-1][1] + stack[-1][2] <= t:
                stack.pop()
            name = stack[-1][0] if stack else "no host event"
            labels.append(name[len(SPAN_PREFIX):]
                          if name.startswith(SPAN_PREFIX) else name)
        return labels

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle seconds of the first device plane, summed by what the host
        was doing in the middle of each gap, largest first."""
        if not self.devices:
            return []
        busy = self.busy_intervals(sorted(self.devices)[0])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        labels = self._host_labels([(a + b) / 2 for a, b in gaps])
        tot: Dict[str, float] = {}
        for (a, b), label in zip(gaps, labels):
            tot[label] = tot.get(label, 0.0) + (b - a)
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s * 1e-9] for name, s in rows]


# -----------------------------------------------------------------------------
# reading the profiler's files
# -----------------------------------------------------------------------------
def _stat(ev, key: str) -> str:
    try:
        return str(dict(ev.stats).get(key, ""))
    except Exception:        # stats that cannot be decoded name nothing
        return ""


def load(trace_dir: str) -> TraceData:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one xplane file under {trace_dir}, "
                         f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:GPU:"):
            ops: List[Event] = []
            modules: List[Event] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, e.start_ns, e.duration_ns,
                                _stat(e, "hlo_module"))
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((e.name, e.start_ns, e.duration_ns, "")
                                   for e in line.events)
            if ops or modules:
                devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            # the harness's spans are on the thread that runs it
            for line in plane.lines:
                events = list(line.events)
                if any(e.name == WINDOW for e in events):
                    host.extend((e.name, e.start_ns, e.duration_ns, "")
                                for e in events)
    return TraceData(devices, host)
