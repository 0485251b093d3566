"""The reader of the fleet engine's on-device batch gather
(``fleet_gather_ms``): on a hand-made trace it reads the gather module and
nothing else, the fleet step and server step readers leave the gather out,
and a trace without the gather (a program that stacks batches on the host)
reads nothing."""
from types import SimpleNamespace

import pytest

from chipbench.harness import common
from chipbench.harness import trace as tr

MS = 1e6        # ns


def _reader(name):
    return common.load_module(common.BENCH_DIR / "metrics" / f"{name}.py")


def _trace(with_gather=True):
    """Two rounds in a 100 ms window; module times in ms."""
    modules = [("jit__fleet_gather(11)", 2, 3), ("jit_fleet_step(12)", 5, 20),
               ("jit__fleet_gather(11)", 30, 3),
               ("jit_fleet_step(12)", 33, 20),
               ("jit_gather(13)", 60, 1), ("jit__step_impl(14)", 62, 8),
               ("jit_scatter(15)", 72, 2)]
    if not with_gather:
        modules = [m for m in modules if "_fleet_gather" not in m[0]]
    dev = {"ops": [["op", a * MS, d * MS, name.split("(")[0]]
                   for name, a, d in modules],
           "modules": [[name, a * MS, d * MS, ""] for name, a, d in modules]}
    return tr.TraceData.from_json({"devices": {"/device:TPU:0": dev},
                                   "host": [[tr.WINDOW, 0, 100 * MS, ""]]})


def _ctx(trace):
    return SimpleNamespace(trace=trace, out={"rounds": 2})


@pytest.mark.parametrize("name,want", [("fleet_gather_ms", 3.0),
                                       ("fleet_step_ms", 20.0),
                                       ("server_step_ms", 5.5)])
def test_the_gather_is_read_by_its_own_metric_alone(name, want):
    assert _reader(name).read(_ctx(_trace())) == pytest.approx(want)


def test_a_host_stacked_run_reads_no_gather():
    assert _reader("fleet_gather_ms").read(_ctx(_trace(False))) is None
    assert _reader("fleet_gather_ms").read(SimpleNamespace(
        trace=None, out={"rounds": 2})) is None
