"""The program's round-phase spans reduced to per-layer numbers.

``run_federated`` (fl/loop.py) and the fleet engines (fl/fleet.py) mark each
round's phases with ``jax.profiler.TraceAnnotation`` spans named ``fl.*``:
``fl.plan``, ``fl.train`` (holding one ``fl.stack`` per chunk of host data,
each holding its device put ``fl.put``), ``fl.account``, ``fl.aggregate``,
``fl.sync`` and ``fl.checkpoint``.  They land in the same profiler session
as the device planes, on the host line that holds ``chipbench:window``
(``trace.load``), so they share the device trace's clock.  A program
without these spans gives no intervals, and every reader here then gives
``None``.

* ``host_ms``: host time inside one span name, per traced round;
* ``idle_share_in``: device idle time inside the union of one span name's
  intervals, over the window, averaged over the device planes as
  ``TraceData.idle_share`` is;
* ``phase_idle``: device idle time by the innermost ``fl.*`` span open,
  ``none`` where no span is open (the spans' coverage of the idle time);
* ``sync_offsets_ms``: how long after the last device operation that
  started before its end each ``fl.sync`` ends (a check that host and
  device share one clock).
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

# outermost first: an inner span's time is taken from the one around it
PHASES = ("fl.plan", "fl.train", "fl.account", "fl.aggregate", "fl.sync",
          "fl.checkpoint", "fl.stack", "fl.put")
NONE = "none"


def intervals(trace, name: str) -> List[Interval]:
    """The spans called ``name``, clipped to the window, in time order."""
    out = []
    for e in trace.host:
        if e[0] == name:
            a, b = max(e[1], trace.t0), min(e[1] + e[2], trace.t1)
            if b > a:
                out.append((a, b))
    return sorted(out)


def union(iv: List[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(iv):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def overlap(segs: List[Interval], busy: List[Interval]) -> List[float]:
    """Length of ``busy`` inside each of ``segs``; both sorted, each list
    disjoint in itself."""
    out, j = [], 0
    for a, b in segs:
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        tot, k = 0.0, j
        while k < len(busy) and busy[k][0] < b:
            tot += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
        out.append(tot)
    return out


def _idle_in(trace, segs: List[Interval]) -> List[float]:
    """Device idle ns in each segment, averaged over the device planes."""
    planes = sorted(trace.devices)
    idle = [0.0] * len(segs)
    for p in planes:
        for i, (seg, busy) in enumerate(
                zip(segs, overlap(segs, trace.busy_intervals(p)))):
            idle[i] += (seg[1] - seg[0]) - busy
    return [x / len(planes) for x in idle]


def host_ms(ctx, name: str) -> Optional[float]:
    """ms per traced round that the host spent inside ``name`` spans."""
    if ctx.trace is None:
        return None
    iv = intervals(ctx.trace, name)
    if not iv:
        return None
    return 1e-6 * sum(b - a for a, b in iv) / ctx.out["rounds"]


def idle_share_in(ctx, name: str) -> Optional[float]:
    """% of the window in which the device idled inside ``name`` spans."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    segs = union(intervals(ctx.trace, name))
    if not segs:
        return None
    t = ctx.trace
    return 100.0 * sum(_idle_in(t, segs)) / (t.t1 - t.t0)


def phase_idle(trace) -> Dict[str, float]:
    """Device idle seconds in the window by the innermost ``fl.*`` span
    open (``fl.train`` and ``fl.stack`` count their own time only), and
    under ``none`` where no span is open; the values sum to the window's
    idle time."""
    edges = sorted({trace.t0, trace.t1} | {x for name in PHASES
                                           for iv in intervals(trace, name)
                                           for x in iv})
    label = [NONE] * (len(edges) - 1)
    for name in PHASES:                   # inner spans paint over outer
        for a, b in intervals(trace, name):
            for i in range(bisect.bisect_left(edges, a),
                           bisect.bisect_left(edges, b)):
                label[i] = name
    segs = list(zip(edges[:-1], edges[1:]))
    out = {name: 0.0 for name in PHASES + (NONE,)}
    if not trace.devices:
        return out
    for name, ns in zip(label, _idle_in(trace, segs)):
        out[name] += ns * 1e-9
    return out


def sync_offsets_ms(trace) -> List[float]:
    """For each ``fl.sync`` wholly inside the window: its end less the end
    of the last device operation that started before it ended (the op
    that ends last, over all planes)."""
    ops = sorted((s, s + d) for plane in trace.devices.values()
                 for _, s, d, _ in plane["ops"])
    starts = [s for s, _ in ops]
    ends, last = [], float("-inf")
    for _, e in ops:
        last = max(last, e)
        ends.append(last)
    out = []
    for e in trace.host:
        if e[0] != "fl.sync" or e[1] < trace.t0 or e[1] + e[2] > trace.t1:
            continue
        end = e[1] + e[2]
        i = bisect.bisect_left(starts, end)
        if i:
            out.append(1e-6 * (end - ends[i - 1]))
    return out


def report(trace) -> dict:
    """Idle time by phase, the share of the window left idle with no
    phase open, and the ``fl.sync`` clock offsets."""
    idle = phase_idle(trace)
    offsets = sync_offsets_ms(trace)
    return {"window_s": trace.window_s,
            "idle_s": idle,
            "uncovered_idle_share": idle[NONE] / trace.window_s,
            "sync_offset_ms": ([min(offsets), max(offsets)]
                               if offsets else None),
            "syncs": len(offsets)}
