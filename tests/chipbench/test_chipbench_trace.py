"""The trace reduction: busy time as the union of operation intervals, the
idle share, device time by module and by kernel name, idle gaps named by
the host span open in them; first on a hand-made trace whose answers are
known, then on a small trace recorded on a TPU v5e, then on a trace this
process records, read from the profiler's own file."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import trace as tr

HERE = Path(__file__).resolve().parent
MS = 1e6        # ns


def _hand_made():
    dev = {"ops": [["fusion.1", 0 * MS, 2 * MS, "jit_fleet_step"],
                   ["fusion.2", 1 * MS, 2 * MS, "jit_fleet_step"],   # overlaps
                   ["topk_kernel", 5 * MS, 1 * MS, "jit__step_impl"],
                   ["quant_kernel", 8 * MS, 1 * MS, "jit__step_impl"],
                   ["fusion.3", 9.5 * MS, 1 * MS, "jit_other"]],       # clipped
           "modules": [["jit_fleet_step(1)", 0, 3 * MS, ""],
                       ["jit__step_impl(2)", 5 * MS, 4 * MS, ""]]}
    host = [[tr.WINDOW, 0, 10 * MS, ""],
            ["chipbench:round", 0, 10 * MS, ""],
            ["PjitFunction(_step_impl)", 3 * MS, 1.5 * MS, ""],
            ["device_put", 6 * MS, 2 * MS, ""]]
    return tr.TraceData.from_json({"devices": {"/device:TPU:0": dev},
                                   "host": host})


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    t = _hand_made()
    assert t.window_s == pytest.approx(0.010)
    # [0,3] + [5,6] + [8,9] + [9.5,10] = 5.5 ms
    assert t.busy_s() == pytest.approx(0.0055)
    assert t.idle_share() == pytest.approx(0.45)


def test_device_time_by_module_and_by_kernel():
    t = _hand_made()
    assert t.module_s(r"fleet_step") == pytest.approx(0.003)
    assert t.module_s(r"_step_impl") == pytest.approx(0.004)
    assert t.op_s(r"topk") == pytest.approx(0.001)
    assert t.op_s(r"quant") == pytest.approx(0.001)
    assert t.top_ops(2)[0][0] == "fusion.1"


def test_idle_gaps_are_named_by_the_innermost_host_event():
    t = _hand_made()
    gaps = dict((name, s) for name, s in t.idle_gaps())
    # [3,5]: middle 4 ms inside the PjitFunction span
    assert gaps["PjitFunction(_step_impl)"] == pytest.approx(0.002)
    # [6,8]: middle 7 ms inside device_put; [9,9.5]: only the round span
    assert gaps["device_put"] == pytest.approx(0.002)
    assert gaps["round"] == pytest.approx(0.0005)
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s())


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.TraceData({}, [["x", 0, 1, ""]])


RECORDED = HERE / "v5e_trace_slice.json"


def _timeline_busy_s(events, t0, t1, step=100.0):
    """Busy seconds by marking a 100 ns timeline: a second method."""
    n = int((t1 - t0) / step)
    busy = np.zeros(n, bool)
    for _, s, d, _ in events:
        a = max(0, int((s - t0) / step))
        b = min(n, int(np.ceil((s + d - t0) / step)))
        busy[a:b] = True
    return busy.sum() * step * 1e-9


def test_a_recorded_v5e_trace_reduces_as_a_second_method_does():
    rec = json.loads(RECORDED.read_text())
    t = tr.TraceData.from_json(rec)
    ops = rec["devices"]["/device:TPU:0"]["ops"]
    assert t.window_s == pytest.approx(0.05)
    assert t.busy_s() == pytest.approx(
        _timeline_busy_s(ops, t.t0, t.t1), abs=2e-6)
    assert 0.0 < t.idle_share() < 1.0
    mods = rec["devices"]["/device:TPU:0"]["modules"]
    fleet = sum(min(s + d, t.t1) - max(s, t.t0) for n, s, d, _ in mods
                if n.startswith("jit_fleet_step"))
    assert t.module_s(r"fleet_step") == pytest.approx(fleet * 1e-9)
    gaps = t.idle_gaps(10 ** 6)
    assert sum(s for _, s in gaps) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-6)


# kernel names as a TPU v5e trace prints them (fleet and server steps)
V5E_NAMES = {
    "topk": '%closed_call.10 = f32[80523,1024]{1,0:T(8,128)} custom-call('
            'f32[80523,1024]{1,0:T(8,128)} %reshape.38, s32[80523,2]{1,0:'
            'T(8,128)S(1)} %copy-done.2), custom_call_target="tpu_custom_'
            'call", operand_layout_constraints={f32[80523,1024]{1,0}, '
            's32[80523,2]{1,0}}',
    "quant": '%quantize.7 = (s8[768,1024]{1,0:T(8,128)(4,1)S(1)}, f32[768,'
             '1]{1,0:T(8,128)S(1)}) custom-call(f32[768,1024]{1,0:T(8,128)'
             'S(1)} %pad.17), custom_call_target="tpu_custom_call"',
    "dequant": '%jvp_jit_dequantize__.11 = f32[8,25600,32]{2,1,0:T(8,128)} '
               'custom-call(s8[8,25600,32]{2,1,0:T(8,128)(4,1)S(1)} %pallas_'
               'call.28, f32[8,25600,1]{2,1,0:T(8,128)} %pallas_call.29), '
               'custom_call_target="tpu_custom_call"',
    "other": '%custom-call.18 = f32[2,8,512,1024]{3,2,1,0:T(8,128)S(1)} '
             'custom-call(f32[2,8,512,1024]{3,2,1,0:T(8,128)} %fusion.1), '
             'custom_call_target="ConcatBitcast"',
}


def test_kernel_patterns_match_the_names_a_v5e_trace_gives():
    from chipbench.harness import common
    import re
    topk = common.load_module(common.BENCH_DIR / "metrics"
                              / "topk_compress_roofline.py").KERNEL
    quant = common.load_module(common.BENCH_DIR / "metrics"
                               / "quant_transfer_roofline.py").KERNEL
    found = {k: (bool(re.search(topk, v)), bool(re.search(quant, v)))
             for k, v in V5E_NAMES.items()}
    assert found == {"topk": (True, False), "quant": (False, True),
                     "dequant": (False, True), "other": (False, False)}


def test_load_reads_the_profilers_file(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("chipbench:round"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert t.window_s > 0
    assert any(e[0] == "chipbench:round" for e in t.host)
    # the CPU has no device plane: nothing is busy, nothing is invented
    assert t.devices == {} and t.busy_s() == 0.0
