"""The plain reference's streamed round (one client at a time, error rows on
the host) against its stacked round (every client side by side, all K
deltas folded in one scan): the same weights and error rows, at small sizes
on the CPU with the streamed path forced by the limit; the choice between
them; and a streamed fold whose device bytes do not grow with K."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import common, fedref, train
from test_chipbench_rehearsal import tiny

REPO = Path(__file__).resolve().parents[2]
BENCH = common.load_benchmark()
SEED = 2147483659

# the LM cases in the qwen3-0.6b-cut4 cell's shape: two OP groups of two
# clients, which the seed interleaves, so the fold order is not the clients'
K4 = dict(clients=4, ops={"1": 2, "2": 2})
# case: (workload, mix overrides, run keywords)
CASES = {
    "lm2-topk-int8": ("qwen3-0.6b-cut4.fed-k4", K4, {}),
    "vgg5-topk-int8": ("vgg5.fedadapt-k64", {}, {}),
    "lm2-dense": ("qwen3-0.6b-cut4.fed-k4",
                  dict(K4, delta_density=1.0, quantize_deltas=False), {}),
    "vgg5-dense-int8": ("vgg5.fedadapt-k64", dict(delta_density=1.0), {}),
    "lm2-bf16": ("qwen3-0.6b-cut4.fed-k4", K4,
                 dict(dtype=jnp.bfloat16, precision="default")),
    "vgg5-half-batch": ("vgg5.fedadapt-k64", {},
                        dict(batch_fn=lambda b: {k: v[:len(v) // 2]
                                                 for k, v in b.items()})),
}
# XLA may keep a bfloat16 intermediate in float32 inside a fusion
# (--xla_allow_excess_precision, on by default), and the two paths fuse
# differently; the bfloat16 case is held to equality with that off, in a
# process of its own
EXCESS_PRECISION_OFF = {"lm2-bf16"}


def _cell(workload, **mix):
    cell = common.Cell(BENCH, workload)
    tiny(cell)
    cell.mix = dict(cell.mix, **mix)
    return cell


def _reference(cell, limit=None, **kw):
    inputs = train.prepare(cell, SEED)
    return fedref.run(cell.ref, cell.spec, cell.mix, inputs["clients"],
                      inputs["ops"], inputs["fl_seed"], limit=limit, **kw)


def _n(cell):
    return sum(a.size for a in jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: cell.ref.init(cell.spec, jax.random.PRNGKey(0)))))


def _host_rows(err, K):
    """The stacked path's (K, ...) device rows as per-client host trees."""
    return [jax.tree_util.tree_map(lambda a: np.asarray(a[i], np.float32),
                                   err) for i in range(K)]


def check(case):
    workload, mix, kw = CASES[case]
    cell = _cell(workload, **mix)
    stacked = _reference(cell, **kw)
    streamed = _reference(cell, limit=1, **kw)
    assert (stacked.path, streamed.path) == ("stacked", "streamed")
    if mix.get("ops") == K4["ops"]:
        ops = train.prepare(cell, SEED)["ops"]
        order = [k for op in dict.fromkeys(ops)
                 for k in range(len(ops)) if ops[k] == op]
        assert order != sorted(order)
    assert len(streamed.weights) == len(stacked.weights) == 4
    for a, b in zip(stacked.weights, streamed.weights):
        jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)
    K = cell.mix["clients"]
    if cell.mix["delta_density"] == 1.0 and not cell.mix["quantize_deltas"]:
        assert streamed.err is None          # no error rows are allocated
        return
    assert len(streamed.err) == K
    moved = 0.0
    for a, b in zip(_host_rows(stacked.err, K), streamed.err):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            assert y.dtype == np.float32
            np.testing.assert_array_equal(x, y)
            moved += float(np.abs(y).sum())
    assert moved > 0


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_streamed_round_matches_the_stacked_one(case):
    if case not in EXCESS_PRECISION_OFF:
        check(case)
        return
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_allow_excess_precision=false").strip())
    out = subprocess.run([sys.executable, __file__, case], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]


def test_the_round_is_streamed_only_where_the_stacked_one_does_not_fit():
    n, K = 1000, 4
    fp = fedref.stacked_bytes(n, K, 4)
    assert fp == (5 * K + 2) * n * 4
    assert fedref.device_bytes_limit() is None          # the CPU says none
    assert fedref.choose(fp, None) == "stacked"
    assert fedref.choose(fp, fp) == "stacked"
    assert fedref.choose(fp, 100 * fp) == "stacked"
    assert fedref.choose(fp, fp - 1) == "streamed"
    # both cells keep the stacked path on a 16 GB chip, at their real sizes
    for workload in ("vgg5.fedadapt-k64", "qwen3-0.6b-cut4.fed-k4"):
        cell = common.Cell(BENCH, workload)
        fp = fedref.stacked_bytes(_n(cell), cell.mix["clients"], 4)
        assert fedref.choose(fp, 15 * 2 ** 30) == "stacked", (workload, fp)
    # the default reference takes it where the device states no limit
    cell = _cell("qwen3-0.6b-cut4.fed-k4", clients=2)
    assert _reference(cell, rounds=1).path == "stacked"


def test_the_streamed_fold_holds_the_same_bytes_at_k2_and_k8(monkeypatch):
    """A streamed round at K=8 under a limit that the stacked footprint
    exceeds; the fold it compiles takes four weight-sized trees and a
    scalar, and the same argument and temporary bytes as at K=2."""
    made = fedref._programs
    sizes = {}

    def spying(*a):
        progs = made(*a)

        def fold(*args):
            if K not in sizes:
                m = progs.fold.lower(*args).compile().memory_analysis()
                sizes[K] = (m.argument_size_in_bytes, m.temp_size_in_bytes)
            return progs.fold(*args)
        return progs._replace(fold=fold)
    monkeypatch.setattr(fedref, "_programs", spying)
    for K in (2, 8):
        cell = _cell("qwen3-0.6b-cut4.fed-k4", clients=K,
                     ops={"1": K // 2, "2": K // 2})
        n = _n(cell)
        limit = 6 * n * 4
        assert fedref.stacked_bytes(n, K, 4) > limit
        out = _reference(cell, limit=limit, rounds=1)
        assert out.path == "streamed" and len(out.err) == K
    assert sizes[2] == sizes[8]
    assert sizes[8][0] == 4 * n * 4 + 4      # acc, p, g, one error row, w_k


def test_the_reference_alone_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "chipbench/reference_memory.py", "--workload",
         "qwen3-0.6b-cut4.fed-k4", "--seed", "1", "--path", "streamed"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr


if __name__ == "__main__":
    check(sys.argv[1])
