"""A short window of every cell through the benchmark's own entry, on the
CPU at a tiny size: the in-process rehearsal (``main(allow_cpu=True)``)
runs set-up, window and comparison and reports ``correct`` but no metric;
the real command refuses a machine without a TPU and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run as bench_run

REPO = Path(__file__).resolve().parents[2]
TINY_LM = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               vocab_size=256)


def tiny(cell):
    """Shrink a cell to a size the CPU runs in seconds."""
    if cell.spec["family"] == "dense":
        cell.spec = dict(cell.spec, **TINY_LM)
    if cell.spec["family"] == "vgg":
        cell.mix = dict(cell.mix, clients=4, samples_per_client=20,
                        batch=10, local_iters=2, eval_samples=20,
                        ops={"2": 1, "4": 1, "5": 1, "7": 1})
    else:
        cell.mix = dict(cell.mix, clients=2, samples_per_client=8, batch=2,
                        seq=32, ops={"1": 1, "2": 1}, eval_samples=4)


def rehearse(workload, capsys, trace=0, patch=None):
    def both(cell):
        tiny(cell)
        if patch is not None:
            patch(cell)
    rc = bench_run.main(["--workload", workload, "--seed", "2147483659",
                         "--seconds", "1", "--trace", str(trace)],
                        allow_cpu=True, patch=both)
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    return line


@pytest.mark.parametrize("workload", ["vgg5.fedadapt-k64",
                                      "qwen3-0.6b-cut4.fed-k4"])
def test_rehearsal_is_correct_and_reports_no_metric(workload, capsys):
    line = rehearse(workload, capsys, trace=int(workload.startswith("vgg")))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_the_command_refuses_a_machine_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "vgg5.fedadapt-k64", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_the_command_fails_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "vgg5.fedadapt-k64", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""


def test_a_traced_run_records_the_first_rounds_of_the_window(monkeypatch,
                                                             tmp_path):
    """The profiler records the window's whole rounds up to ``TRACE_S``
    seconds in and stops there; the window runs on and counts every
    round."""
    import time
    from chipbench.harness import common, trace, train
    cell = common.Cell(common.load_benchmark(), "vgg5.fedadapt-k64")
    tiny(cell)
    monkeypatch.setattr(train, "TRACE_S", 0.5)
    out = train.run(cell, 2147483659, 2.0, str(tmp_path),
                    common.CompileCounter().install(), time.perf_counter())
    assert 0 < out["traced"]["rounds"] < out["rounds"]
    assert out["traced"]["window_s"] >= 0.5
    data = trace.load(str(tmp_path))
    assert data.window_s == pytest.approx(out["traced"]["window_s"],
                                          abs=0.05)
