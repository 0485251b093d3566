"""The readers of the program's round-phase spans (harness/spans.py and the
five metrics on it): first on a hand-made trace whose answers are known,
with spans clipped at both edges of the window, adjacent and nested spans
and two device planes; then on a window without any ``fl.*`` span; then on
a trace this process records of a small ``run_federated`` run."""
import time
from types import SimpleNamespace

import jax
import pytest

from chipbench.harness import common, spans
from chipbench.harness import trace as tr

MS = 1e6        # ns
METRICS = ("host_stack_ms", "host_put_ms", "host_wait_ms",
           "idle_share.stack", "idle_share.aggregate")


def _reader(name):
    return common.load_module(common.BENCH_DIR / "metrics" / f"{name}.py")


def _hand_made(with_spans=True):
    """Two traced rounds in a 100 ms window (times in ms)."""
    def ops(*iv):
        return [["op", a * MS, (b - a) * MS, "jit_x"] for a, b in iv]
    devices = {"/device:TPU:0": {"ops": ops((10, 12), (18, 19), (25, 40),
                                            (55, 60), (60, 88)),
                                 "modules": []},
               "/device:TPU:1": {"ops": ops((0, 40), (60, 70)),
                                 "modules": []}}
    host = [(tr.WINDOW, 0, 100), ("np.stack", 6, 4), ("fl.sync", -10, 2)]
    if with_spans:
        host += [("fl.plan", -5, 8),             # clipped at the start
                 ("fl.train", 3, 47),
                 ("fl.stack", 5, 15), ("fl.put", 15, 5),
                 ("fl.stack", 20, 15),            # adjacent to the first
                 ("fl.put", 30, 4),
                 ("fl.account", 50, 1),           # [51, 52] has no span
                 ("fl.aggregate", 52, 18),
                 ("fl.sync", 70, 20),
                 ("fl.plan", 90, 5),
                 ("fl.train", 95, 15),            # clipped at the end
                 ("fl.stack", 96, 9)]
    host = [[name, a * MS, d * MS, ""] for name, a, d in host]
    return tr.TraceData.from_json({"devices": devices, "host": host})


def _ctx(trace):
    return SimpleNamespace(trace=trace, out={"rounds": 2})


def test_the_five_metrics_read_the_known_answers():
    got = {m: _reader(m).read(_ctx(_hand_made())) for m in METRICS}
    want = {
        # stacks clipped: 15 + 15 + 4 ms over 2 rounds
        "host_stack_ms": 17.0,
        "host_put_ms": 4.5,                       # (5 + 4) / 2
        "host_wait_ms": 10.0,                     # the whole-window sync
        # union [5, 35] + [96, 100]: plane 0 idles 21 ms, plane 1 4 ms
        "idle_share.stack": 12.5,
        # [52, 70]: plane 0 idles 3 ms, plane 1 8 ms
        "idle_share.aggregate": 5.5,
    }
    assert got == pytest.approx(want)


def test_idle_time_by_innermost_phase_sums_to_the_idle_time():
    t = _hand_made()
    idle = spans.phase_idle(t)
    want_ms = {"fl.plan": 6.5, "fl.train": 12.0, "fl.stack": 10.5,
               "fl.put": 2.0, "fl.account": 1.0, "fl.aggregate": 5.5,
               "fl.sync": 11.0, "fl.checkpoint": 0.0, "none": 1.0}
    assert {k: v * 1e3 for k, v in idle.items()} == pytest.approx(want_ms)
    assert sum(idle.values()) == pytest.approx(t.window_s - t.busy_s())
    rep = spans.report(t)
    assert rep["uncovered_idle_share"] == pytest.approx(0.01)


def test_sync_offsets_count_only_syncs_inside_the_window():
    # the sync ends at 90 ms, the last operation started before it at 88
    assert spans.sync_offsets_ms(_hand_made()) == pytest.approx([2.0])
    assert spans.report(_hand_made())["sync_offset_ms"] == \
        pytest.approx([2.0, 2.0])


def test_a_window_without_phase_spans_reads_none():
    t = _hand_made(with_spans=False)
    assert {m: _reader(m).read(_ctx(t)) for m in METRICS} == \
        {m: None for m in METRICS}
    idle = spans.phase_idle(t)
    assert idle["none"] == pytest.approx(t.window_s - t.busy_s())
    assert spans.report(t)["sync_offset_ms"] is None


def test_load_returns_the_programs_spans_on_the_window_line(tmp_path):
    from repro.configs.vgg import VGGConfig
    from repro.data.synthetic import make_cifar_like, split_clients
    from repro.fl.loop import FLConfig, run_federated
    cfg = VGGConfig(name="vgg-tiny", layers=("C4", "MP", "FC10"), ops=(2, 3),
                    input_hw=8)
    clients = split_clients(make_cifar_like(40, seed=0, hw=8), 2)
    test = make_cifar_like(10, seed=1, hw=8)
    fl = FLConfig(rounds=2, local_iters=1, batch_size=5, engine="batched")
    run_federated(cfg, clients, test, fl)           # compile outside
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        run_federated(cfg, clients, test, fl)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    names = [e[0] for e in t.host if e[0].startswith("fl.")]
    for phase in ("fl.plan", "fl.train", "fl.stack", "fl.put",
                  "fl.account", "fl.aggregate", "fl.sync"):
        assert names.count(phase) == 2, phase
    ctx = _ctx(t)
    for m in ("host_stack_ms", "host_put_ms", "host_wait_ms"):
        assert 0.0 < _reader(m).read(ctx) < 1e3 * wall
    # the CPU has no device plane: the idle shares read nothing
    assert _reader("idle_share.stack").read(ctx) is None
    assert _reader("idle_share.aggregate").read(ctx) is None
