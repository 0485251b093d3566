"""Round-phase profiler spans of ``run_federated`` and the fleet engines:
each round emits its phases once, in order and without overlap; the
engines' host-data spans nest inside ``fl.train`` (one per chunk, or one per
client-iteration) with their device puts inside them, and the puts' byte
counts are what the engine puts (the batched engine's rows and flips, the
sequential engine's batches); and tracing changes no result."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.vgg import VGGConfig
from repro.data.synthetic import make_cifar_like, split_clients
from repro.fl.loop import FLConfig, run_federated
from repro.fl.planner import Planner

TINY = VGGConfig(name="vgg-tiny", layers=("C4", "MP", "C8", "MP", "FC16",
                                          "FC10"),
                 ops=(2, 4, 6), input_hw=8)
K, ITERS, BATCH, ROUNDS = 6, 2, 5, 3
ROUND_PHASES = ["fl.plan", "fl.train", "fl.account", "fl.aggregate",
                "fl.sync"]


class TwoGroups(Planner):
    """Even clients cut at OP 2, odd ones at OP 4: two OP groups."""

    def plan(self, round_idx, last_times, bandwidths):
        return [2 if k % 2 == 0 else 4 for k in range(len(last_times))]


def _run(engine, ckpt_dir):
    clients = split_clients(make_cifar_like(K * 20, seed=0, hw=8), K)
    test = make_cifar_like(20, seed=9, hw=8)
    fl = FLConfig(rounds=ROUNDS, local_iters=ITERS, batch_size=BATCH,
                  engine=engine, checkpoint_dir=str(ckpt_dir),
                  checkpoint_every=2)
    return run_federated(TINY, clients, test, fl, planner=TwoGroups())


def _spans(trace_dir):
    """Every ``fl.*`` event of the trace: (name, start, end, args)."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fl."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module", params=["batched", "sequential"])
def runs(request, tmp_path_factory):
    tmp = tmp_path_factory.mktemp(request.param)
    with jax.profiler.trace(str(tmp / "trace")):
        traced = _run(request.param, tmp / "ck_on")
    plain = _run(request.param, tmp / "ck_off")
    return request.param, _spans(str(tmp / "trace")), traced, plain


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_each_round_emits_its_phases_once_in_order(runs):
    _, spans, _, _ = runs
    phases = [s for s in spans if s[0] in ROUND_PHASES]
    assert [s[0] for s in phases] == ROUND_PHASES * ROUNDS
    assert [s[3]["round"] for s in phases] == \
        [r for r in range(ROUNDS) for _ in ROUND_PHASES]
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1], (a, b)          # siblings, no overlap
    ckpt = [s for s in spans if s[0] == "fl.checkpoint"]
    assert [s[3]["round"] for s in ckpt] == [1]
    sync1 = [s for s in phases if s[0] == "fl.sync"][1]
    assert sync1[2] <= ckpt[0][1]


def test_stack_spans_nest_in_their_rounds_training(runs):
    engine, spans, _, _ = runs
    trains = [s for s in spans if s[0] == "fl.train"]
    stacks = [s for s in spans if s[0] == "fl.stack"]
    per_round = [[s for s in stacks if _inside(s, t)] for t in trains]
    assert sum(len(p) for p in per_round) == len(stacks)
    if engine == "batched":
        # one chunk per OP group: three clients at OP 2, three at OP 4
        want = [(2, 3), (4, 3)]
    else:
        # one per client-iteration, clients in order
        want = [(2 if k % 2 == 0 else 4, 1) for k in range(K)
                for _ in range(ITERS)]
    for p in per_round:
        assert [(s[3]["op"], s[3]["clients"]) for s in p] == want


def test_put_spans_nest_in_stack_spans_with_the_stacked_bytes(runs):
    engine, spans, _, _ = runs
    stacks = [s for s in spans if s[0] == "fl.stack"]
    puts = [s for s in spans if s[0] == "fl.put"]
    assert len(puts) == len(stacks)
    if engine == "batched":
        # the fleet's data is on the device: an int32 row and a flip flag
        sample = 4 + 1
    else:
        sample = 8 * 8 * 3 * 4 + 4           # float32 image, int32 label
    for stack, put in zip(stacks, puts):
        assert _inside(put, stack)
        draws = stack[3]["clients"] * (ITERS if engine == "batched" else 1)
        assert put[3]["bytes"] == draws * BATCH * sample


def test_tracing_changes_no_result(runs):
    _, _, traced, plain = runs
    for key in ("accuracy", "ops", "round_time", "dropped"):
        np.testing.assert_array_equal(traced[key], plain[key])
    for a, b in zip(jax.tree_util.tree_leaves(traced["params"]),
                    jax.tree_util.tree_leaves(plain["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
