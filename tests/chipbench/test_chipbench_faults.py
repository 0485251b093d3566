"""The comparison that decides ``correct`` fails a broken program: each
fault a cell can have is planted under the timed path of a CPU rehearsal,
and ``correct`` comes out false.  The precision control (the reference in
bfloat16 put in the program's place) fails the comparison too."""
import pytest

from chipbench.harness import common, train
from test_chipbench_rehearsal import rehearse, tiny


def test_a_server_step_that_returns_its_state_unchanged_fails(
        monkeypatch, capsys):
    from repro.fl.flatbuf import ServerStep
    monkeypatch.setattr(ServerStep, "__call__",
                        lambda self, g, deltas, w, err=None, masks=None:
                        (g, err))
    line = rehearse("vgg5.fedadapt-k64", capsys)
    assert line["correct"] is False
    assert line["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_of_each_batch_left_out_fails(monkeypatch, capsys):
    from repro.fl import fleet
    full = fleet._sgd_update

    def half(program, quantize, params, batch, lr, op):
        batch = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return full(program, quantize, params, batch, lr, op)
    monkeypatch.setattr(fleet, "_sgd_update", half)
    line = rehearse("qwen3-0.6b-cut4.fed-k4", capsys)
    assert line["correct"] is False


@pytest.mark.parametrize("workload", ["vgg5.fedadapt-k64",
                                      "qwen3-0.6b-cut4.fed-k4"])
def test_the_bfloat16_control_fails_the_training_comparison(workload):
    cell = common.Cell(common.load_benchmark(), workload)
    tiny(cell)
    readings = train.control_readings(cell, 2147483659)
    for name in ("bf16", "half_batch"):
        failed = [k for k, limit in cell.limits.items()
                  if readings[name][k] > limit]
        assert failed, (name, readings[name], cell.limits)
